"""Make ``repro`` importable when an example is run straight from a
checkout (``python examples/quickstart.py``) without the documented
``PYTHONPATH=src`` prefix.

The documented invocation stays canonical::

    PYTHONPATH=src python examples/quickstart.py

With the prefix set (or the package installed) this helper is a no-op; the
fallback resolves ``src/`` relative to this file, so it also works from any
working directory — unlike the old per-script ``sys.path.insert(0, "src")``
hack, which silently broke outside the repo root. It also turns on JAX's
persistent compilation cache (``repro.launch.cache``) before the example's
first compile.
"""
import os
import sys


def ensure_repro_on_path() -> None:
    try:
        import repro  # noqa: F401  (already importable: PYTHONPATH / install)
        return
    except ImportError:
        pass
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), os.pardir, "src"))
    if src not in sys.path:
        sys.path.insert(0, src)


ensure_repro_on_path()

from repro.launch.cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
