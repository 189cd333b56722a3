"""The benchmark's CPU tests: the CPU backend, and the harness's own
directory on the import path."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import tiny  # noqa: E402,F401  (puts bench/ and src/ on sys.path)
