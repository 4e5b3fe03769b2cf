"""Plain references of the configurations' models, one module per
configuration's `reference` key (found by `harness.family`).

They import nothing of the program under test and are given nothing it
made: weights come from `bench/harness/weights.py` at the run's seed, laid
out by the configuration's family module.
"""
