"""Run manifest: the one description of "what produced this artifact".

The launch ``--trace`` exports (the Perfetto trace and its
``metrics.json`` snapshot) stamp the same dict, built here. Keys:
backend + device count, jax/jaxlib versions, python/platform, seed, git
sha.

Must import (and run) on jax-free hosts, so the jax block is
best-effort: a missing accelerator stack degrades to ``backend: None``,
never an ImportError.
"""
from __future__ import annotations

import pathlib
import platform
import subprocess
from typing import Any, Dict, Optional

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def _git_sha() -> Optional[str]:
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=_REPO_ROOT,
                             capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.SubprocessError):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def manifest(seed: Optional[int] = None, **extra: Any) -> Dict[str, Any]:
    """The run manifest stamped into every obs export.

    ``seed`` is recorded when the producing run has one; ``extra``
    key/values ride along verbatim (a run's own knobs — sizes, point
    names — belong in its results, not here)."""
    out: Dict[str, Any] = {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "git_sha": _git_sha(),
        "jax": None,
        "jaxlib": None,
        "backend": None,
        "device_count": None,
    }
    try:
        import jax
        import jaxlib
        out["jax"] = jax.__version__
        out["jaxlib"] = jaxlib.__version__
        out["backend"] = jax.default_backend()
        out["device_count"] = jax.device_count()
    except Exception:        # no accelerator stack: manifest still valid
        pass
    if seed is not None:
        out["seed"] = int(seed)
    out.update(extra)
    return out
