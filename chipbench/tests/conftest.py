import os

# the benchmark's tests run on the CPU: run.py's chip check is bypassed
# where a test drives a run, and Pallas kernels are not reached
os.environ.setdefault("JAX_PLATFORMS", "cpu")
