"""Global jit-compile cache keyed on plan structure.

Ref analog: none in the reference (DataFusion interprets plans); this is the
TPU-specific cost center called out in SURVEY.md §7(f): AQE re-plans every
stage, so per-stage compiled pipelines must be cached across tasks. jax.jit
already caches per (shapes, dtypes) *per function object*; operators are
rebuilt per task, so we key the function object itself on the plan's
structural key — same plan + same shape bucket => zero recompiles.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Callable, Dict, Hashable, Optional

import jax

from blaze_tpu.config import conf
from blaze_tpu.runtime import placement, trace

_lock = threading.Lock()
_cache: Dict[Hashable, Callable] = {}
_stats = {"hits": 0, "misses": 0}
# single observer slot (runtime/compile_service registers its shape
# registry here): called as observer(event, key, ns) with event in
# {"hit", "miss", "compiled"} — outside _lock, exceptions swallowed.
_observer = None


def set_observer(fn) -> None:
    global _observer
    _observer = fn


def _notify(event: str, key: Hashable, ns: int = 0) -> None:
    obs = _observer
    if obs is not None:
        try:
            obs(event, key, ns)
        except Exception:
            pass


_NAME_MAX = 64


def kind_of(key: Hashable) -> str:
    """The program kind of a cache key: its first element when the key is
    a tuple that starts with a string, else "other" — the vocabulary of
    compile_service's per-kind statistics and, through `_named`, of the
    device trace's module names."""
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        return key[0]
    return "other"


def _named(fn: Callable, name: str) -> Callable:
    """`fn` under `name`, for jax.jit to name the HLO module after
    (`jit_<name>`): a thin wrapper, so a function shared between keys
    (executor's `pack`) is never renamed in place. functools.wraps keeps
    `__wrapped__`, which is where jax.jit resolves static_argnames /
    donate_argnames against the real signature."""
    @functools.wraps(fn)
    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = name
    return named


def get_or_compile(key: Hashable, make_fn: Callable[[], Callable],
                   jit: bool = True, name: Optional[str] = None,
                   **jit_kwargs) -> Callable:
    """Return a jitted function for `key`, building it once.

    The program is named by its kind (`kind_of(key)`): every module in a
    device trace reads `jit_<kind>(<hash>)`. Kinds are the fixed strings
    already in the keys. A catch-all kind may pass `name` to say more
    (`fused.filter.project`): it must start with the kind, come from the
    plan's structure only (never a literal, a shape or data: a name is
    part of the persistent cache's key) and is cut to a fixed length.

    `jit=False` caches the bare callable instead: used for pipelines with
    host-evaluated expressions (digests/JSON/UDF), which run op-at-a-time
    on concrete arrays (hostfns.host_apply) rather than inside one
    compiled program.

    On a task placed on a chip (runtime/placement.py) the key gains that
    chip's id: the executable is the chip's own (jax's persistent-cache
    key keeps the device assignment on TPU), so its first call there is a
    compile and is counted as one. Unplaced callers, and every caller on
    a one-chip host, keep their keys."""
    kind = kind_of(key)
    dev = placement.current()
    if dev is not None:
        tag = ("@dev", dev.id)
        key = key + (tag,) if isinstance(key, tuple) else (key, tag)
    with _lock:
        fn = _cache.get(key)
        if fn is not None:
            _stats["hits"] += 1
    if fn is not None:
        _notify("hit", key)
        return fn
    with _lock:
        _stats["misses"] += 1
    _notify("miss", key)
    from blaze_tpu.runtime import faults

    faults.inject("jit.compile")
    if jit:
        name = (name or kind)[:_NAME_MAX]

        def build():
            return jax.jit(_named(make_fn(), name), **jit_kwargs)

        built = _with_stale_exec_retry(key, build(), build)
        built = _with_first_call_timer(key, built, kind)
    else:
        built = make_fn()
    with _lock:
        return _cache.setdefault(key, built)


def _with_first_call_timer(key, fn, kind):
    """Report the first invocation's wall time as this key's compile cost.

    jax compiles lazily at the first jitted call, so the first-call wall
    clock is trace + XLA build (+ the first dispatch enqueue; the result
    is NOT blocked on — blocking here would serialize the engine's async
    dispatch pipelines, and compile time dwarfs enqueue time anyway).

    With tracing on, every call is also a `dispatch` span (kind;
    first_call on the compiling one): the host's time in the call, which
    returns when the work is enqueued — never the device's time. Off, the
    steady path pays one attribute read and builds no span object.
    """
    done = []

    @functools.wraps(fn)
    def timed(*args, **kwargs):
        if done:
            if not conf.trace_enabled:
                return fn(*args, **kwargs)
            with trace.span("dispatch", program=kind):
                return fn(*args, **kwargs)
        done.append(True)
        with trace.span("dispatch", program=kind, first_call=True):
            t0 = time.perf_counter_ns()
            out = fn(*args, **kwargs)
            _notify("compiled", key, time.perf_counter_ns() - t0)
        return out

    return timed


def _with_stale_exec_retry(key, fn, build):
    """Self-healing wrapper for a rare XLA dispatch inconsistency.

    Re-executing a cached jitted fn on inputs with identical pytree /
    avals / shardings can fail with `INVALID_ARGUMENT: Execution supplied
    N buffers but compiled program expected M buffers` (observed on the
    forced-multi-device CPU backend with struct-backed columns; the
    executable's captured-constant accounting goes stale). A fresh trace
    of the same program always succeeds, so on that specific error we
    evict, rebuild once, and re-dispatch — correctness is unaffected and
    steady-state cost is zero. `build` makes the jitted program anew,
    under the same name."""
    with _lock:
        holder = _retry.setdefault(key, [fn])

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        try:
            return holder[0](*args, **kwargs)
        # raised as ValueError on some paths and as XlaRuntimeError (a
        # RuntimeError subclass) on others — match by message
        except (ValueError, RuntimeError) as e:
            if "buffers but compiled program expected" not in str(e):
                raise
            with _lock:
                _stats["stale_exec_rebuilds"] = \
                    _stats.get("stale_exec_rebuilds", 0) + 1
                holder[0] = build()
            return holder[0](*args, **kwargs)

    return wrapped


_retry: Dict[Hashable, list] = {}


def stats() -> Dict[str, int]:
    with _lock:
        return dict(_stats)


def clear() -> None:
    with _lock:
        _cache.clear()
        _retry.clear()
        _stats.update(hits=0, misses=0)
