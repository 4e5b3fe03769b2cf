"""Plain references of the configurations' models, one module per family.

They import nothing of the program under test and are given nothing it
made: weights come from `bench/harness/weights.py` at the run's seed.
"""
