import os

# the benchmark's tests run on the CPU; the harness itself refuses it
os.environ.setdefault("JAX_PLATFORMS", "cpu")
