import os

# Tests and benches must see exactly ONE device (the dry-run sets its own
# XLA_FLAGS before importing jax — see src/repro/launch/dryrun.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
