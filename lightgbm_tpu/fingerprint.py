"""Environment fingerprints: who produced a result, and on what world.

`fingerprint()` names the commit, the jax/jaxlib versions, the device
kind and count and every set `LGBM_TPU_*` flag — cheaply and without ever
raising (a caller must not die because git is absent). A path choice
(HIST_F32, BINS_I32, ...) is an experiment axis, so it belongs in it.

`world_fingerprint()` is the distributed-world block checkpoint.py stamps
into every sidecar.
"""
from __future__ import annotations

import os
import subprocess
from typing import Any, Dict, Optional


def _git_sha(repo_dir: Optional[str] = None) -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=repo_dir or os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        if out.returncode == 0:
            return out.stdout.strip()
    except Exception:
        pass
    return "unknown"


def _flag_env() -> Dict[str, str]:
    """Every set LGBM_TPU_* flag plus JAX_PLATFORMS."""
    keep_exact = ("JAX_PLATFORMS",)
    out = {k: v for k, v in os.environ.items()
           if k.startswith("LGBM_TPU_") or k in keep_exact}
    return dict(sorted(out.items()))


def fingerprint(repo_dir: Optional[str] = None) -> Dict[str, Any]:
    """The environment identity block. Pure observation, never raises;
    unknown fields degrade to "unknown" (no jax on the path, no git
    checkout) rather than failing the caller."""
    fp: Dict[str, Any] = {
        "git_sha": _git_sha(repo_dir),
        "flags": _flag_env(),
    }
    try:
        import jax

        fp["jax_version"] = str(jax.__version__)
        try:
            import jaxlib

            fp["jaxlib_version"] = str(jaxlib.__version__)
        except Exception:
            fp["jaxlib_version"] = "unknown"
        try:
            devs = jax.devices()
            fp["device_kind"] = str(devs[0].device_kind) if devs else "none"
            fp["device_count"] = len(devs)
            fp["backend"] = str(jax.default_backend())
        except Exception:
            fp["device_kind"] = "unknown"
            fp["device_count"] = 0
            fp["backend"] = "unknown"
    except Exception:
        fp["jax_version"] = "unknown"
        fp["jaxlib_version"] = "unknown"
        fp["device_kind"] = "unknown"
        fp["device_count"] = 0
        fp["backend"] = "unknown"
    return fp


def world_fingerprint() -> Dict[str, Any]:
    """The distributed-world identity block stamped into checkpoint
    sidecars (docs/ROBUSTNESS.md, "Distributed fault domain"): enough to
    name BOTH shapes when a restore lands on a different world than the
    save. Same contract as fingerprint(): pure observation, never raises,
    unknown fields degrade to safe defaults."""
    fp: Dict[str, Any] = {
        "process_count": 1,
        "mesh_shape": [1],
        "device_kinds": ["unknown"],
        "jax_version": "unknown",
        "jaxlib_version": "unknown",
    }
    try:
        import jax

        fp["jax_version"] = str(jax.__version__)
        try:
            import jaxlib

            fp["jaxlib_version"] = str(jaxlib.__version__)
        except Exception:
            pass
        fp["process_count"] = int(jax.process_count())
        devs = jax.devices()
        fp["mesh_shape"] = [len(devs)]
        fp["device_kinds"] = sorted({str(d.device_kind) for d in devs}) \
            or ["none"]
    except Exception:
        pass
    return fp
