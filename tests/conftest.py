import os

# Tests and benches must see ONE device; a test that needs more forces host
# devices in a subprocess of its own (tests/test_fleet.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Tier-1 strictness (DESIGN.md §2.11): silent rank promotion is how weak
# broadcast bugs slip into the traced hot paths — every jnp op in the
# suite must broadcast with explicit ranks.
os.environ.setdefault("JAX_NUMPY_RANK_PROMOTION", "raise")

import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
