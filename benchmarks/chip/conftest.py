import os

# The benchmark's own tests run on the host's CPU; the chip runs go through
# run.py, which refuses any device that is not a TPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
