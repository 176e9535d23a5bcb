"""Where the program runs — the one module that answers it.

Four decisions live here and nowhere else:

* ``on_tpu()`` — "is this the chip": the default JAX platform is ``tpu``,
  nothing else.  Every backend/precision/block-size default that differs
  between the chip and the CPU test platform asks this.
* ``pallas_interpret()`` — the single seam for Pallas interpret mode: kernels
  compile through Mosaic on the chip and run interpreted everywhere else
  (the CPU test platform).  ``lowering_for("tpu")`` flips both answers so
  the CPU box can AOT-compile the real programs for a TPU topology
  (tests/test_tpu_aot_compile.py).
* ``configure_compile_cache()`` — where compiled programs persist.
* ``child_env()`` / ``require_chips()`` — what a spawned process is told
  about its platform.  A chip belongs to ONE process: a parent that has
  initialised JAX holds it, so children that need the chip are launched
  from parents that stayed off JAX, with their platform passed explicitly.
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Any, Dict, Iterator, Optional

from .utils.log import LightGBMError, log_warning

CHECKOUT = Path(__file__).resolve().parents[1]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_lowering_platform: Optional[str] = None   # set only inside lowering_for()


# -- platform ---------------------------------------------------------------
def platform_name() -> str:
    """The platform programs are lowered for: JAX's default backend, or the
    ``lowering_for`` target during an AOT compile check."""
    if _lowering_platform is not None:
        return _lowering_platform
    import jax
    return jax.default_backend()


def on_tpu() -> bool:
    return platform_name() == "tpu"


def pallas_interpret() -> bool:
    return not on_tpu()


@contextlib.contextmanager
def lowering_for(platform: str) -> Iterator[None]:
    """Resolve engine defaults and lower Pallas kernels as ``platform`` would
    while running on another host — for ``.lower().compile()`` against a
    ``jax.experimental.topologies`` device only; nothing traced inside may
    be executed here.  jit caches are dropped on entry and exit because a
    cached trace does not know which side of the seam it was made on."""
    global _lowering_platform
    import jax
    jax.clear_caches()
    prev, _lowering_platform = _lowering_platform, platform
    try:
        yield
    finally:
        _lowering_platform = prev
        jax.clear_caches()


def device_record() -> Dict[str, Any]:
    """The device a result came from, as JAX reports it — merged into every
    benchmark record so a CPU number can never pass for a chip number."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def require_tpu(what: str) -> Dict[str, Any]:
    """``device_record()`` on the chip; raises naming the platform found."""
    rec = device_record()
    if rec["platform"] != "tpu":
        raise LightGBMError(
            f"{what} needs a TPU but JAX found platform "
            f"{rec['platform']!r} ({rec['device_kind']} x "
            f"{rec['device_count']})")
    return rec


def check_device_type(device_type: str) -> None:
    """``device_type=tpu`` (the default) is a request, not a fact: say so
    once when the device JAX found is something else."""
    want = str(device_type).strip().lower()
    have = platform_name()
    if want in ("tpu", "cpu") and want != have:
        _warn_device_mismatch(want, have)


@functools.lru_cache(maxsize=None)
def _warn_device_mismatch(want: str, have: str) -> None:
    log_warning(f"device_type={want} but JAX's default platform is "
                f"{have!r}: training runs on {have}")


# -- compile cache ----------------------------------------------------------
def compile_cache_dir() -> str:
    """The persistent compile cache directory and nothing else: the
    environment's if ``JAX_COMPILATION_CACHE_DIR`` is set, otherwise
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part of
    the cache key and a directory that moves never hits."""
    return os.environ.get(CACHE_ENV) or str(CHECKOUT / ".jax_cache")


def configure_compile_cache() -> str:
    """Called by every entry point and every spawned process.  With the
    environment variable set, JAX reads it itself and nothing is set in
    code; otherwise point JAX at the checkout's cache.  Returns the
    directory in use."""
    if not os.environ.get(CACHE_ENV):
        import jax
        path = compile_cache_dir()
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
    return compile_cache_dir()


# -- processes --------------------------------------------------------------
def backend_initialized() -> bool:
    """Has this process created a JAX backend (and so taken the chip, if
    there is one)?  Importing jax does not; ``jax.devices()`` does."""
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


_PROBE = ("import json, jax; d = jax.devices(); print(json.dumps({"
          "'platform': d[0].platform, 'device_kind': d[0].device_kind, "
          "'device_count': len(d)}))")


def probe_devices(timeout: float = 120.0) -> Dict[str, Any]:
    """``device_record()`` taken in a throw-away subprocess, so the caller
    never initialises JAX and the chip is free again when this returns.
    Must not be called from a process that already holds the chip."""
    r = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                       text=True, timeout=timeout)
    try:
        return json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        raise LightGBMError(
            f"device probe failed (rc {r.returncode}): "
            f"{r.stderr.strip()[-500:]}") from None


def child_platform() -> str:
    """Platform for spawned workers/replicas when the caller names none:
    this process's own if it says (``JAX_PLATFORMS``, or the backend it
    already initialised), else whatever a probe finds."""
    env = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if env:
        return env
    if backend_initialized():
        return platform_name()
    return probe_devices()["platform"]


def require_chips(n_procs: int, platform: str, what: str) -> None:
    """Fail at launch — not after a startup timeout — when ``n_procs``
    chip-holding processes cannot each have a chip."""
    if platform != "tpu":
        return
    if backend_initialized():
        raise LightGBMError(
            f"{what}: this process has initialised JAX and holds the chip, "
            "so no child can open it — a chip belongs to one process. "
            "Launch from a process that stays off JAX, or run the children "
            "on the CPU (platform='cpu')")
    have = probe_devices()
    chips = have["device_count"] if have["platform"] == "tpu" else 0
    if n_procs > chips:
        raise LightGBMError(
            f"{what}: {n_procs} chip-holding processes were asked for and "
            f"this host has {chips} chip(s) — a chip belongs to one "
            "process. Ask for fewer, or run them on the CPU "
            "(platform='cpu')")


def child_env(platform: str, n_cpu_devices: int = 0,
              base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Environment for a spawned process: its platform stated explicitly
    (never inherited by accident), the compile cache placed where this
    process's is, and the checkout importable.  ``n_cpu_devices`` > 0 asks
    for that many virtual CPU devices (``platform='cpu'`` only)."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = platform
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    if n_cpu_devices > 0:
        if platform != "cpu":
            raise LightGBMError("virtual devices exist on the CPU platform "
                                f"only, not on {platform!r}")
        flags.append(f"--xla_force_host_platform_device_count={n_cpu_devices}")
    if flags:
        env["XLA_FLAGS"] = " ".join(flags)
    else:
        env.pop("XLA_FLAGS", None)
    env[CACHE_ENV] = compile_cache_dir()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(CHECKOUT), env.get("PYTHONPATH", "")) if p)
    return env
