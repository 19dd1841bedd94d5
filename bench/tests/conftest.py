"""The harness's tests run on the CPU at tiny sizes: the chip is only
for the benchmark's own runs."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
