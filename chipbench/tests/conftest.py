"""Puts the program's ``src`` on the path and keeps the tests on the CPU."""

import os
import sys

from chipbench import manifest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if str(manifest.ROOT / "src") not in sys.path:
    sys.path.insert(0, str(manifest.ROOT / "src"))
