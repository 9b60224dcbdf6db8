"""The yardstick: what turns a run's clocks, counters and trace into numbers.

Later PRs change the program, never these files.
"""
