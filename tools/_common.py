"""Shared preamble for the offline diagnostic tools.

Every tool in this directory needs the same two things before it can
import the package from a source checkout: the repo root on ``sys.path``
and the persistent compile cache enabled once jax is importable (so
repeated diagnostic runs skip recompiles). This module is the one place
they live.

Usage (first import in each tool, before any ``cruise_control_tpu``
import)::

    import _common  # noqa: F401  (side effect: sys.path)
    ...
    _common.enable_cache()        # after this, import the package
"""

from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def enable_cache() -> str | None:
    """Enable the persistent compile cache ($JAX_COMPILATION_CACHE_DIR,
    else <checkout>/.jax_cache; imports jax, so call it where the tool
    is ready to pay backend init)."""
    from cruise_control_tpu import enable_persistent_compile_cache
    return enable_persistent_compile_cache()
