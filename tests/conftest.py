import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

try:  # optional dependency: fall back to fixed, seeded examples
    import hypothesis  # noqa: F401
except ImportError:
    import _hypothesis_stub
    _hypothesis_stub.install()

# The suite runs on the CPU, also on a machine with a chip: the chip stays
# free for one process, and the dry-run subprocess inherits the setting.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

# CPU tests must see exactly 1 device (the dry-run subprocess sets its own
# XLA_FLAGS); keep everything deterministic and in f32.
jax.config.update("jax_enable_x64", False)
