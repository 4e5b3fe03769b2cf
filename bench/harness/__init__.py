"""The benchmark's yardstick: everything the cells share.

`run.py` is the entry point. The modules here load `BENCHMARK.json` and the
files it names (`spec`), generate traffic (`traffic`) and drive the served
engine with it (`loadgen`), build the system under test from a
configuration file (`system`, `weights`), reduce the client's timestamps
(`measure`), check what it served against the plain reference (`check`,
`../reference/`; `faults` plants faults for the tests and for
`control.py`'s readings), count the work a step requires (`work`,
`peaks`) and reduce a profiler trace (`tracing`).
"""
