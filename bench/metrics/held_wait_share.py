"""USF scheduler: share of the window in which the decode workers held
their slots waiting on the device (``device_wait_s`` delta, summed over
the servers, over window x servers), in %."""


def value(rec):
    return 100.0 * rec["counters"]["device_wait_s"] / (
        rec["client"]["seconds"] * rec["servers"])
