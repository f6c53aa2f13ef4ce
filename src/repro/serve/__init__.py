"""Serving: the in-process engine (``serve.engine``) and its multi-process
twin (``serve.multiproc``). Import the submodule you need; the package
imports nothing, so a gateway process that only needs ``multiproc`` stays
off JAX."""
