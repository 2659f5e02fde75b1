"""The yardstick: everything the benchmark measures with, kept out of the
program so that no later PR can change it.  Imports nothing of pinot_tpu
except in `cluster.py`, which builds the system under test."""
