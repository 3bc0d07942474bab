"""The in-process cluster (``cluster.SimCluster``): a mon and N OSDs on
one event loop, with the kill / revive / wait helpers the benchmark's
drivers, ``chip_smoke.py``, ``tools/chaos.py`` and the tests share.

The load generator this package was named for is gone (traffic is
``benchmark/traffic`` + ``benchmark/drivers``); the import path stays
because the benchmark's files name it.
"""
