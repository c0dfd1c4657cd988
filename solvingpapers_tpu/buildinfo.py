"""Process identity for observability surfaces: version, git sha, uptime.

A scraped replica is anonymous without this — ROADMAP item 2's
per-replica `/statusz` aggregation needs to know WHICH build and WHICH
jax it is talking to before any of its numbers mean anything.

`build_info()` is cheap after the first call (git sha and versions are
cached; only uptime is live) and never raises: a missing git binary, a
tarball install, or an uninitialized jax backend degrade to None
fields, not a 500 from `/statusz`.
"""

from __future__ import annotations

import functools
import os
import subprocess
import sys
import time

__all__ = ["build_info", "git_sha"]

# process start, stamped at first import (the engine imports this before
# serving starts, so "uptime" is serving-process age for all practical
# purposes)
_START_MONOTONIC = time.monotonic()
_START_UNIX = time.time()


@functools.lru_cache(maxsize=1)
def git_sha() -> str | None:
    """The repo HEAD this process is running from, or None when the
    package runs outside a git checkout (wheel/tarball installs)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5,
        )
    except (OSError, subprocess.SubprocessError):
        return None
    if out.returncode != 0:
        return None
    sha = out.stdout.strip()
    return sha or None


@functools.lru_cache(maxsize=1)
def _static_info() -> dict:
    from solvingpapers_tpu import __version__

    info: dict = {
        "package": "solvingpapers_tpu",
        "version": __version__,
        "git_sha": git_sha(),
        "python": sys.version.split()[0],
        "pid": os.getpid(),
        "started_unix": round(_START_UNIX, 3),
    }
    try:
        import jax

        info["jax"] = jax.__version__
    except Exception:  # pragma: no cover - jax is a hard dep in practice
        info["jax"] = None
    try:
        import jaxlib

        info["jaxlib"] = getattr(jaxlib, "__version__", None)
    except Exception:
        info["jaxlib"] = None
    try:
        import jax

        dev = jax.devices()[0]
        info["platform"] = dev.platform
        info["device_kind"] = dev.device_kind
        info["n_devices"] = len(jax.devices())
    except Exception:
        info["platform"] = None
        info["device_kind"] = None
        info["n_devices"] = None
    return info


def build_info() -> dict:
    """The /statusz `build` section: static identity + live uptime."""
    return {
        **_static_info(),
        "uptime_s": round(time.monotonic() - _START_MONOTONIC, 3),
    }
