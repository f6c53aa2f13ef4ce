"""Multi-process serving (repro.serve.multiproc): N model-server
processes behind one gateway, node slots brokered across them. The tests
that spawn servers are slow: each child initializes its own JAX runtime."""

import pytest

from repro.serve import multiproc


# --------------------------------------------------------------------- #
# one chip per server process (no child spawned)
# --------------------------------------------------------------------- #
def test_chip_env_binds_one_chip_per_process():
    envs = [multiproc.chip_env(i) for i in range(4)]
    for i, env in enumerate(envs):
        assert env["TPU_VISIBLE_CHIPS"] == str(i)
        # a per-process bound below the host's: libtpu loads once per chip
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_ADDRESSES"] == (
            f"localhost:{env['TPU_PROCESS_PORT']}")
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4


def test_tpu_chip_count_reads_pci_and_dev_and_honours_jax_platforms(
        tmp_path, monkeypatch):
    devices = [("0x1ae0", "0x0063")] * 4 + [
        ("0x1ae0", "0x0042"),   # a Google NIC, not a TPU
        ("0x8086", "0x0063"),   # another vendor
    ]
    for i, (vendor, device) in enumerate(devices):
        d = tmp_path / f"0000:00:{i:02x}.0"
        d.mkdir()
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
    nodes = ["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/2", "/dev/vfio/3"]

    def fake_glob(pattern):
        if pattern.startswith("/sys/bus/pci"):
            return [str(p) for p in tmp_path.glob("*/vendor")]
        return nodes if pattern.startswith("/dev/vfio") else []

    monkeypatch.setattr(multiproc.glob, "glob", fake_glob)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert multiproc.tpu_chip_count() == 4
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert multiproc.tpu_chip_count() == 4
    # one chip of a four-chip host handed to this VM: one device node
    nodes[1:] = []
    assert multiproc.tpu_chip_count() == 1
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert multiproc.tpu_chip_count() == 0


@pytest.mark.parametrize("chips,n_servers,expect", [
    (0, 3, [None, None, None]),   # CPU host: the path the tests take
    (4, 2, [0, 1]),               # server i takes chip i
    (2, 3, None),                 # more servers than chips: refused
])
def test_gateway_gives_server_i_chip_i(monkeypatch, chips, n_servers,
                                       expect):
    monkeypatch.setattr(multiproc, "tpu_chip_count", lambda: chips)
    archs = {f"srv{i}": "smollm_360m" for i in range(n_servers)}
    if expect is None:
        with pytest.raises(multiproc.ServerProcessError, match="one TPU chip"):
            multiproc.MultiProcessGateway(archs)
        return
    gw = multiproc.MultiProcessGateway(archs, coordinate=False)
    assert [s.spec["chip"] for s in gw.servers] == expect


@pytest.mark.slow
@pytest.mark.parametrize("coordinate", [True, False])
def test_multiprocess_gateway_serves(coordinate):
    """Requests fan out to every server process and join; with
    coordination the broker splits the node, without it the processes run
    free — both complete (coordination is never a liveness dependency)."""
    from repro.serve.multiproc import MultiProcessGateway

    gw = MultiProcessGateway(
        {"srv-a": "smollm_360m", "srv-b": "qwen1_5_110b"},
        coordinate=coordinate, node_capacity=2, slots_per_server=2,
        max_batch=2, max_len=32, smoke=True)
    try:
        gw.start(ready_timeout=300.0)
        if coordinate:
            snap = gw.broker.snapshot()
            assert sorted(snap["workers"]) == ["srv-a", "srv-b"]
            assert sum(w["granted"] for w in snap["workers"].values()) == 2
        for _ in range(2):
            rec = gw.handle([5, 6, 7], max_new=3, timeout=300.0)
            assert rec["latency"] > 0
            assert sorted(rec["outputs"]) == ["srv-a", "srv-b"]
            for out in rec["outputs"].values():
                assert len(out) == 3
        assert len(gw.responses) == 2
        for s in gw.servers:
            assert s.device["count"] >= 1
            # the reference check runs in the child, queued behind requests
            chk = s.reference_check([5, 6, 7], rec["outputs"][s.name], 0.0)
            assert chk["ok"] and chk["positions"] == 3
        if coordinate:
            # each server pump reported its brokered grant with results
            assert all(s.served == 2 for s in gw.servers)
    finally:
        gw.stop()


@pytest.mark.slow
def test_dead_server_process_surfaces_not_hangs():
    """Unsupervised (the PR 5 fail-fast contract): a server process killed
    mid-flight raises ServerProcessError at the caller (and, under
    coordination, its node lease is reclaimed)."""
    from repro.serve.multiproc import MultiProcessGateway, ServerProcessError

    gw = MultiProcessGateway(
        {"srv-a": "smollm_360m", "srv-b": "qwen1_5_110b"},
        coordinate=True, node_capacity=2, slots_per_server=2,
        max_batch=2, max_len=32, smoke=True, supervise=False)
    try:
        gw.start(ready_timeout=300.0)
        gw.handle([5, 6], max_new=2, timeout=300.0)  # warm + sane
        victim = gw.servers[0]
        victim._proc.kill()
        victim._proc.join(10.0)
        with pytest.raises((ServerProcessError, TimeoutError)):
            gw.handle([5, 6], max_new=2, timeout=60.0)
        # the broker reclaimed the dead server's node lease
        import time

        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            workers = gw.broker.snapshot()["workers"]
            if list(workers) == ["srv-b"]:
                break
            time.sleep(0.1)
        assert list(gw.broker.snapshot()["workers"]) == ["srv-b"]
    finally:
        gw.stop()


# --------------------------------------------------------------------- #
# supervision: restart, crash-loop breaker, in-flight retry (PR 6)
# --------------------------------------------------------------------- #
def _wait_until(cond, timeout, step=0.1):
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


@pytest.mark.slow
def test_supervisor_restarts_dead_server_then_breaker_benches_crashloop():
    """A killed server is respawned (capped backoff) and serves again; a
    crash-looping server trips the circuit breaker — the slot is marked
    failed in snapshots and requests keep routing to the survivors."""
    from repro.serve.multiproc import MultiProcessGateway

    gw = MultiProcessGateway(
        {"srv-a": "smollm_360m", "srv-b": "qwen1_5_110b"},
        coordinate=True, node_capacity=2, slots_per_server=2,
        max_batch=2, max_len=32, smoke=True,
        supervise=True, max_restarts=2, restart_window=600.0,
        restart_backoff=(0.1, 0.4), poll_interval=0.1)
    try:
        gw.start(ready_timeout=300.0)
        gw.handle([5, 6], max_new=2, timeout=300.0)  # warm + sane
        victim = gw.servers[0]

        # phase 1: heal — a dead server is restarted and serves again
        victim._proc.kill()
        assert _wait_until(lambda: victim.restarts >= 1 and victim.alive(),
                           timeout=300.0)
        rec = gw.handle([5, 6], max_new=2, timeout=300.0)
        assert sorted(rec["outputs"]) == ["srv-a", "srv-b"]
        assert rec["retried"] == {}
        snap = gw.snapshot()
        assert snap["servers"]["srv-a"]["restarts"] >= 1
        assert snap["servers"]["srv-a"]["failed"] is False

        # phase 2: crash loop — every respawn now dies during init, so
        # the window fills and the breaker opens (slot benched, routed
        # around), instead of burning the node respawning forever
        victim.spec["arch"] = "no-such-arch"
        victim._proc.kill()
        assert _wait_until(lambda: victim.failed, timeout=300.0)
        snap = gw.snapshot()
        assert snap["servers"]["srv-a"]["failed"] is True
        rec = gw.handle([5, 6], max_new=2, timeout=300.0)
        assert list(rec["outputs"]) == ["srv-b"]  # survivors keep serving
    finally:
        gw.stop()


@pytest.mark.slow
def test_inflight_request_retried_once_on_survivor():
    """A request in flight on a dying server is retried once on a
    survivor and recorded under the dead server's key with a
    ``retried_on`` marker, instead of surfacing ServerProcessError."""
    from repro.serve.multiproc import MultiProcessGateway

    # quiescent supervisor (long poll): the restart machinery must not
    # race the deterministic in-flight window this test pins below
    gw = MultiProcessGateway(
        {"srv-a": "smollm_360m", "srv-b": "qwen1_5_110b"},
        coordinate=True, node_capacity=2, slots_per_server=2,
        max_batch=2, max_len=32, smoke=True,
        supervise=True, poll_interval=60.0)
    try:
        gw.start(ready_timeout=300.0)
        gw.handle([5, 6], max_new=2, timeout=300.0)  # warm + sane
        victim = gw.servers[0]
        victim._proc.kill()
        victim._proc.join(30.0)
        # pin the window: the gateway targets the (already dead) server
        # exactly once more, so the submitted request is provably in
        # flight on a dead process when the collector reaches it
        forced = []
        real_alive = victim.alive

        def one_last_alive():
            if not forced:
                forced.append(1)
                return True
            return real_alive()

        victim.alive = one_last_alive
        try:
            rec = gw.handle([5, 6], max_new=2, timeout=300.0)
        finally:
            victim.alive = real_alive
        assert sorted(rec["outputs"]) == ["srv-a", "srv-b"]
        assert rec["retried"] == {"srv-a": "srv-b"}
    finally:
        gw.stop()
