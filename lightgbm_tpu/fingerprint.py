"""Environment fingerprint + the append-only bench ledger.

A bench number with no provenance is noise: 2.38M row-iters/s means
nothing until you know which commit, which jax, which device, and which
`LGBM_TPU_*` kernel flags produced it. `fingerprint()` captures exactly
that — cheaply and without ever raising (a capture must not die because
git is absent) — and bench.py stamps it into every record.

`append_ledger()` is the durable trail: one fingerprinted record per
line in BENCH_LEDGER.jsonl, appended via checkpoint.py's atomic
read-modify-replace so a crash mid-capture never tears the file.
tools/benchdiff.py reads the ledger back and gates PRs on it; the record
schema is documented in docs/OBSERVABILITY.md and versioned by
``LEDGER_SCHEMA_VERSION`` so readers can reject records they predate.
"""
from __future__ import annotations

import os
import subprocess
from typing import Any, Dict, Optional

# bump on any breaking change to the bench-record key set; benchdiff
# refuses to compare records across major schema versions
LEDGER_SCHEMA_VERSION = 1

DEFAULT_LEDGER = "BENCH_LEDGER.jsonl"
ENV_LEDGER = "BENCH_LEDGER"  # path override; "0"/"off" disables appends


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
    """Every set LGBM_TPU_* flag plus the jax/bench knobs that change what
    a capture measures — a path choice (HIST_F32, BINS_I32, ...) is an
    experiment axis, so it belongs in the fingerprint."""
    keep_exact = ("JAX_PLATFORMS",)
    out = {k: v for k, v in os.environ.items()
           if k.startswith("LGBM_TPU_") or k in keep_exact}
    return dict(sorted(out.items()))


def fingerprint(repo_dir: Optional[str] = None) -> Dict[str, Any]:
    """The environment identity block stamped on every bench record.
    Pure observation, never raises; unknown fields degrade to "unknown"
    (no jax on the path, no git checkout) rather than failing a capture."""
    fp: Dict[str, Any] = {
        "git_sha": _git_sha(repo_dir),
        "schema_version": LEDGER_SCHEMA_VERSION,
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


def ledger_path(repo_dir: Optional[str] = None) -> Optional[str]:
    """Resolved ledger file path, or None when appends are disabled via
    $BENCH_LEDGER=0/off/empty-string-sentinel."""
    env = os.environ.get(ENV_LEDGER)
    if env is not None:
        if env.strip().lower() in ("0", "off", "none", ""):
            return None
        return env
    base = repo_dir or os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    return os.path.join(base, DEFAULT_LEDGER)


def append_ledger(record: Dict[str, Any],
                  path: Optional[str] = None) -> Optional[str]:
    """Append one JSON record line to the ledger (atomic whole-file
    replace — the ledger stays a few thousand lines, so rewrite cost is
    irrelevant next to crash consistency). Returns the path written, or
    None when the ledger is disabled."""
    import json

    from .checkpoint import atomic_write_text

    if path is None:
        path = ledger_path()
    if path is None:
        return None
    prior = ""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            prior = fh.read()
        if prior and not prior.endswith("\n"):
            prior += "\n"
    except FileNotFoundError:
        pass
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    line = json.dumps(record, sort_keys=True) + "\n"
    atomic_write_text(path, prior + line)
    return path
