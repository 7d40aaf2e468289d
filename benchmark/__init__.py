"""The benchmark: ``python3 benchmark/run.py --workload <config>.<traffic>``.

Everything that decides a number lives here (traffic generation, the plain
reference, the comparison behind ``correct``, the trace reduction, the
table of peaks, the bytes-needed function); from ``bqueryd_tpu`` the
benchmark takes only the system under test and its spans and counters.
"""
