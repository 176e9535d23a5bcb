"""Deterministic fault injection for the robustness test matrix.

Faults are declared in the ``LGBTPU_CHAOS`` environment variable and fire
at exact, reproducible points of the training loop — the same strategy the
reference uses for its network tests (tests/distributed simulates worker
loss with localhost process kills), generalized into one harness the unit
tests and manual experiments share.

Grammar (directives separated by ``;``, options by ``,``)::

    LGBTPU_CHAOS="kill:iter=5,rank=1,once=/run/m"   # os._exit after iter 5
    LGBTPU_CHAOS="nan_grad:iter=3,count=8"          # NaN one gradient batch
    LGBTPU_CHAOS="truncate_snapshot"                # corrupt snapshot files
    LGBTPU_CHAOS="hang:iter=3,rank=1,once=/run/m"   # stop heartbeating
    LGBTPU_CHAOS="heartbeat_delay:seconds=2"        # slow every heartbeat

Closed-loop pipeline faults (docs/ROBUSTNESS.md "Closed-loop
freshness"; ``iter`` for ``poison_refit`` is the 1-based tree index of
the refit loop)::

    LGBTPU_CHAOS="poison_refit:iter=1,count=4"      # NaN refit leaf values
    LGBTPU_CHAOS="kill_refit:once=/run/m"           # die between gate and pointer
    LGBTPU_CHAOS="torn_pointer:once=/run/m"         # truncated promote.json write

Serving-fleet faults (docs/SERVING.md fleet architecture; ``rank`` here
is the REPLICA rank — the supervisor exports ``LGBTPU_REPLICA_RANK`` to
every replica process and rank matching prefers it over
``jax.process_index``; ``iter`` is the replica's heartbeat-loop beat
number, one beat every ~0.25 s)::

    LGBTPU_CHAOS="kill_replica:iter=8,rank=0,once=/run/m"  # SIGKILL-like exit
    LGBTPU_CHAOS="hang_replica:iter=12,rank=1,once=/run/m" # wedge the replica
    LGBTPU_CHAOS="slow_replica:seconds=0.5"                # delay every request
    LGBTPU_CHAOS="drop_conn:count=3"                       # reset 3 connections

Options:

* ``iter=N``   — fire at boosting iteration N (1-based); omitted = every.
* ``rank=R``   — only in the process with ``jax.process_index() == R``
  (or ``LGBTPU_REPLICA_RANK == R`` in serving replicas).
* ``once=P``   — marker-file latch: fire only if P does not exist, and
  create P first, so a relaunched/resumed cohort is not killed again.
* ``seconds=S``/``count=N`` — directive-specific magnitudes.

Every hook re-reads the env var (cheap dict lookup + cached parse), so
tests can monkeypatch it per-case; with the variable unset every hook is
an exact no-op.  Run ``python -m lightgbm_tpu.robustness.chaos`` to print
the parsed directive table.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import List, Optional

from ..utils.log import log_warning

ENV_VAR = "LGBTPU_CHAOS"


@dataclass
class Directive:
    name: str
    iteration: Optional[int] = None
    rank: Optional[int] = None
    once: Optional[str] = None
    seconds: Optional[float] = None
    count: Optional[int] = None


def _parse(text: str) -> List[Directive]:
    out: List[Directive] = []
    for raw in text.split(";"):
        raw = raw.strip()
        if not raw:
            continue
        name, _, opts = raw.partition(":")
        d = Directive(name=name.strip())
        for tok in opts.split(","):
            tok = tok.strip()
            if not tok:
                continue
            key, _, val = tok.partition("=")
            key = key.strip()
            if key in ("iter", "iteration"):
                d.iteration = int(val)
            elif key == "rank":
                d.rank = int(val)
            elif key == "once":
                d.once = val
            elif key == "seconds":
                d.seconds = float(val)
            elif key == "count":
                d.count = int(val)
            else:
                raise ValueError(
                    f"{ENV_VAR}: unknown option {key!r} in directive {raw!r}")
        out.append(d)
    return out


_cache_text: Optional[str] = None
_cache: List[Directive] = []


def directives() -> List[Directive]:
    """Parsed directives for the CURRENT env value (re-read every call)."""
    global _cache_text, _cache
    text = os.environ.get(ENV_VAR, "")
    if text != _cache_text:
        _cache = _parse(text)
        _cache_text = text
    return _cache


def active() -> bool:
    return bool(directives())


def has(name: str) -> bool:
    return any(d.name == name for d in directives())


def _rank_matches(d: Directive) -> bool:
    if d.rank is None:
        return True
    # serving replicas carry their rank in the environment (set by the
    # fleet supervisor); importing jax for process_index would be both
    # wrong (replicas are single-process jax) and expensive here
    env_rank = os.environ.get("LGBTPU_REPLICA_RANK")
    if env_rank is not None:
        try:
            return int(env_rank) == d.rank
        except ValueError:
            return False
    import jax
    return jax.process_index() == d.rank


def _fire_once(d: Directive) -> bool:
    """Marker-file latch: created BEFORE firing so even an os._exit cannot
    re-arm the directive for the relaunched cohort."""
    if d.once is None:
        return True
    if os.path.exists(d.once):
        return False
    try:
        with open(d.once, "w") as fh:
            fh.write(f"fired {d.name} at {time.time()}\n")
    except OSError:
        pass
    return True


def _matches(d: Directive, name: str, iteration: Optional[int]) -> bool:
    if d.name != name:
        return False
    if d.iteration is not None and d.iteration != iteration:
        return False
    return _rank_matches(d)


def maybe_kill(iteration: int) -> None:
    """Simulate a hard crash/preemption right after ``iteration``: exits the
    process with no cleanup (``os._exit``), like SIGKILL would."""
    for d in directives():
        if _matches(d, "kill", iteration) and _fire_once(d):
            log_warning(f"chaos: killing process at iteration {iteration}")
            os._exit(137)


def inject_nan_grad(grad, iteration: int):
    """Poison the first ``count`` gradient rows with NaN at the matching
    iteration (1-based: pass ``iter_ + 1``); identity otherwise."""
    for d in directives():
        if _matches(d, "nan_grad", iteration) and _fire_once(d):
            import jax.numpy as jnp
            n = min(d.count or 8, grad.shape[0])
            log_warning(f"chaos: injecting NaN into {n} gradient rows at "
                        f"iteration {iteration}")
            return grad.at[:n].set(jnp.nan)
    return grad


def maybe_truncate_snapshot(path: str, iteration: Optional[int] = None) -> None:
    """Corrupt a just-written snapshot (cut the file in half) to exercise
    the manifest-checksum rejection path at resume time."""
    for d in directives():
        if _matches(d, "truncate_snapshot", iteration) and _fire_once(d):
            size = os.path.getsize(path)
            with open(path, "r+b") as fh:
                fh.truncate(max(size // 2, 1))
            log_warning(f"chaos: truncated snapshot {path} "
                        f"({size} -> {max(size // 2, 1)} bytes)")


def heartbeat_hook(iteration: int) -> None:
    """Called by the worker heartbeat callback before each beat: ``hang``
    stops beating (sleeps ~forever, the supervisor's hang detector must
    reap the worker); ``heartbeat_delay`` just slows the beat down."""
    for d in directives():
        if _matches(d, "hang", iteration) and _fire_once(d):
            log_warning(f"chaos: hanging worker at iteration {iteration}")
            time.sleep(d.seconds or 3600.0)
        elif _matches(d, "heartbeat_delay", iteration):
            time.sleep(d.seconds or 1.0)


# ---------------------------------------------------------------------------
# closed-loop pipeline faults (docs/ROBUSTNESS.md "Closed-loop freshness")
# ---------------------------------------------------------------------------

def inject_nan_refit(values: "np.ndarray", tree_index: int):
    """Poison the first ``count`` refitted leaf values of tree
    ``tree_index`` (1-based) with NaN — the validation gate's nan_guard
    must refuse the candidate; identity otherwise."""
    for d in directives():
        if _matches(d, "poison_refit", tree_index) and _fire_once(d):
            import numpy as np
            n = min(d.count or 4, values.shape[0])
            log_warning(f"chaos: poisoning {n} refit leaf values of tree "
                        f"{tree_index}")
            out = np.array(values, np.float64, copy=True)
            out[:n] = np.nan
            return out
    return values


def maybe_kill_refit() -> None:
    """Simulate the pipeline process dying BETWEEN gate-pass and the
    promotion pointer write (``os._exit``, like SIGKILL): the fleet must
    keep serving the old generation because the pointer never moved."""
    for d in directives():
        if _matches(d, "kill_refit", None) and _fire_once(d):
            log_warning("chaos: killing pipeline between gate and "
                        "pointer write")
            os._exit(137)


def maybe_tear_pointer(fleet_dir: str, pointer_text: str,
                       name: str = "promote.json") -> bool:
    """Replace the atomic promotion-pointer write with a NON-atomic
    truncated write (first half of the JSON) — simulates a promoter dying
    mid-write on a filesystem without atomic rename.  ``name`` selects
    the pointer file (per-tenant pointers are ``promote_<id>.json``).
    Replicas must treat the torn pointer as unreadable and keep serving.
    Returns True when fired (the caller must then skip its own pointer
    write)."""
    for d in directives():
        if _matches(d, "torn_pointer", None) and _fire_once(d):
            path = os.path.join(fleet_dir, name)
            torn = pointer_text[:max(len(pointer_text) // 2, 1)]
            with open(path, "w") as fh:
                fh.write(torn)
            log_warning(f"chaos: tore pointer write at {path} "
                        f"({len(pointer_text)} -> {len(torn)} bytes)")
            return True
    return False


# ---------------------------------------------------------------------------
# serving-fleet faults (docs/SERVING.md "Fleet architecture")
# ---------------------------------------------------------------------------

class DropConnection(Exception):
    """Raised by :func:`request_hook` when ``drop_conn`` fires; the HTTP
    handler closes the client socket without a response, so the client
    sees a connection reset — the fanout front must absorb it as a
    retryable transport error."""


# a wedged replica stays wedged: once hang_replica fires, EVERY later
# request (and the beat loop) blocks, like a process stuck in a lock
_replica_hung = False

# drop_conn with count=N resets only the first N matching requests; the
# latch is per-process (each replica counts its own drops)
_drops_fired = 0


def replica_hung() -> bool:
    return _replica_hung


def replica_beat_hook(beat: int) -> None:
    """Called by the fleet replica's heartbeat loop before each beat
    (one beat every ~0.25 s; ``iter`` matches the beat number).

    ``kill_replica`` exits the process with no cleanup (SIGKILL-like);
    ``hang_replica`` wedges the whole replica: the beat loop blocks (the
    supervisor's stale-heartbeat detector must reap it) and every request
    thread blocks too (the front's deadline/breaker must route around
    it)."""
    global _replica_hung
    for d in directives():
        if _matches(d, "kill_replica", beat) and _fire_once(d):
            log_warning(f"chaos: killing serving replica at beat {beat}")
            os._exit(137)
        elif _matches(d, "hang_replica", beat) and _fire_once(d):
            log_warning(f"chaos: hanging serving replica at beat {beat}")
            _replica_hung = True
            time.sleep(d.seconds or 3600.0)


def request_hook() -> None:
    """Called by the serving request path before any work.

    ``slow_replica`` delays the request by ``seconds``; ``drop_conn``
    raises :class:`DropConnection` (``count`` bounds how many requests
    are reset); a replica wedged by ``hang_replica`` blocks here forever
    — a hung process answers nothing, not just its heartbeat."""
    global _drops_fired
    if _replica_hung:
        time.sleep(3600.0)
    for d in directives():
        if _matches(d, "slow_replica", None) and _fire_once(d):
            time.sleep(d.seconds or 0.5)
        elif _matches(d, "drop_conn", None):
            if d.count is not None and _drops_fired >= d.count:
                continue
            if not _fire_once(d):
                continue
            _drops_fired += 1
            log_warning("chaos: dropping serving connection "
                        f"({_drops_fired}{'/' + str(d.count) if d.count else ''})")
            raise DropConnection()


def main() -> int:
    ds = directives()
    if not ds:
        print(f"{ENV_VAR} is unset or empty: all chaos hooks are no-ops")
        return 0
    print(f"{ENV_VAR}={os.environ.get(ENV_VAR, '')!r}")
    print(f"{'directive':<18}{'iter':<8}{'rank':<8}{'seconds':<10}"
          f"{'count':<8}once")
    for d in ds:
        print(f"{d.name:<18}{str(d.iteration):<8}{str(d.rank):<8}"
              f"{str(d.seconds):<10}{str(d.count):<8}{d.once}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
