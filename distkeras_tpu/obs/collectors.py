"""JAX-specific telemetry collectors.

Three signals XLA-land owns that generic counters can't see:

* **Backend compiles** — ``jax.monitoring`` listeners, installed when
  ``distkeras_tpu.obs`` is imported, feed process-global totals
  (``compile_totals()``: count + seconds, persistent-cache hits and
  misses, tracing and lowering seconds) and the **compile log**
  (``compile_log()``): one entry for each
  ``/jax/core/compile/backend_compile_duration`` event, naming the
  program, its three stage times (trace, lower, backend: the XLA
  compile or the load of a cached executable), whether the persistent
  cache answered, and the ``obs.span`` path that was open when it
  happened. Compile seconds are the "unproductive" term in the goodput
  accounting (``obs.tape``). The listeners cost a dict and an append
  per compile and nothing per step: a warm step path compiles nothing.
* **Per-function recompiles** — ``RecompileDetector.watch(name, fn)``
  tracks a jitted function's executable-cache size
  (``fn._cache_size()``). After ``mark_warm()`` any growth means the
  hot step recompiled — the classic shape-leak bug (a Python int
  promoted to a fresh traced shape, a ragged batch, a dtype drift) —
  and ``check()`` raises a ``RecompileWarning`` naming the function,
  with the seconds and the span of the compile from the log.
  Growth BEFORE warm-up is normal (first-call compiles, one program per
  legitimate shape bucket).
* **Device-memory watermarks** — ``memory_watermark()`` folds
  ``utils.profiling.device_memory_stats`` into per-device gauges whose
  ``max`` field is the high-water mark across calls.
"""

from __future__ import annotations

import collections
import re
import threading
import warnings
import weakref
from typing import Dict, List, Optional

from distkeras_tpu.obs.spans import current_path
from distkeras_tpu.utils.profiling import now

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_STAGE = {_TRACE_EVENT: "trace_s", _LOWER_EVENT: "lower_s"}
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

#: entries the compile log keeps; older ones are dropped and counted
#: (``compile_totals()["overflow"]``), so a long-lived server cannot
#: grow it
MAX_LOG = 4096

_lock = threading.Lock()
_totals = {"count": 0, "seconds": 0.0, "hits": 0, "misses": 0,
           "trace_s": 0.0, "lower_s": 0.0, "overflow": 0}
_log: collections.deque = collections.deque(maxlen=MAX_LOG)
_listener_installed = [False]
# per compiling thread: how deep inside tracing/lowering it is, the
# outermost trace/lower seconds by program not yet given to an entry,
# and what the persistent cache said inside the open backend event
_tls = threading.local()
_API_WRAPPER = re.compile(r"^\w+\((.*)\)$")


class RecompileWarning(UserWarning):
    """A watched jitted function recompiled after warm-up."""


def _program(fun_name) -> str:
    """JAX names a traced function ``f`` and its module ``jit(f)``:
    one name for both, the function's own."""
    m = _API_WRAPPER.match(str(fun_name))
    return m.group(1) if m else str(fun_name)


def _no_stages() -> Dict[str, float]:
    return dict.fromkeys(_STAGE.values(), 0.0)


def _on_scalar(name: str, value, **kw) -> None:
    # JAX records a stage's start as a scalar of the same name
    if name in _STAGE:
        _tls.depth = getattr(_tls, "depth", 0) + 1
    elif name == _COMPILE_EVENT:
        _tls.cache = None


def _on_event(name: str, **kw) -> None:
    # cache_misses alone will not do: JAX records it only when it
    # writes the entry; and it "requests" the cache of every compile,
    # also where no directory was given it
    if name == _CACHE_REQUEST:
        import jax
        if jax.config.jax_compilation_cache_dir:
            _tls.cache = "miss"
    elif name == _CACHE_HIT:
        _tls.cache = "hit"


def _on_event_duration(name: str, duration: float, fun_name="",
                       **kw) -> None:
    stage = _STAGE.get(name)
    if stage is not None:
        depth = _tls.depth = max(getattr(_tls, "depth", 1) - 1, 0)
        if depth:
            return      # a function traced inside another: in its time
        if not hasattr(_tls, "pending"):
            _tls.pending = {}
        stages = _tls.pending.setdefault(_program(fun_name), _no_stages())
        stages[stage] += float(duration)
        with _lock:
            _totals[stage] += float(duration)
    elif name == _COMPILE_EVENT:
        program = _program(fun_name)
        stages = getattr(_tls, "pending", {}).pop(program, _no_stages())
        cache = getattr(_tls, "cache", None)
        entry = {"program": program, "backend_s": float(duration),
                 **stages, "cache": cache,
                 "t_end": now(), "span": current_path()}
        _tls.cache = None
        with _lock:
            _totals["count"] += 1
            _totals["seconds"] += float(duration)
            if cache is not None:
                _totals["hits" if cache == "hit" else "misses"] += 1
            if len(_log) == _log.maxlen:
                _totals["overflow"] += 1
            _log.append(entry)


def install_compile_listener() -> None:
    """Idempotent: register the ``jax.monitoring`` listeners feeding
    the process-global compile totals and the compile log."""
    if _listener_installed[0]:
        return
    _listener_installed[0] = True
    import jax.monitoring
    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(
        _on_event_duration)


def compile_totals() -> Dict[str, float]:
    """Process-global totals since the listeners were installed:
    ``count`` and ``seconds`` of backend compiles (a load from the
    persistent cache counts as one), ``hits`` and ``misses`` of the
    persistent cache, ``trace_s`` and ``lower_s`` of all tracing and
    lowering (outermost functions only, and also where no compile
    followed: ``jax.eval_shape``, ``.lower()`` alone), and the
    ``overflow``: entries the log dropped at its bound."""
    with _lock:
        return dict(_totals)


def compile_log() -> List[Dict]:
    """A copy of the compile log, oldest first: one dict for each
    backend compile, ``{"program", "backend_s", "trace_s", "lower_s",
    "cache", "t_end", "span"}``. ``cache`` is ``"hit"``,
    ``"miss"`` or ``None`` (no persistent cache was asked), ``t_end``
    is on ``utils.profiling.now``'s clock and ``span`` the
    ``obs.current_path()`` at the event (``()`` outside any span, and
    always with telemetry disabled). While nothing was dropped its
    length is ``compile_totals()["count"]`` and its ``backend_s`` add
    up to ``["seconds"]``."""
    with _lock:
        return [dict(e) for e in _log]


class RecompileDetector:
    """Tracks executable-cache growth of named jitted functions.

    Lifecycle: ``watch`` each hot function right after building it,
    ``mark_warm()`` once the warm-up call(s) ran, then ``check()``
    periodically (each epoch / every N serving iterations). ``check``
    warns ONCE per observed growth step, so a leak that recompiles
    every step does not also flood stderr every step.

    Holds jitted functions via weakref where the callable supports it
    (falling back to a strong reference otherwise) so watching never
    extends an executable's lifetime.
    """

    def __init__(self, registry=None):
        from distkeras_tpu.obs import get_registry
        self.registry = registry if registry is not None else get_registry()
        self._watched: Dict[str, Dict] = {}
        self._lock = threading.Lock()

    @staticmethod
    def _cache_size(fn) -> Optional[int]:
        try:
            return int(fn._cache_size())
        except Exception:
            return None

    def watch(self, name: str, fn) -> None:
        """Track ``fn`` (a ``jax.jit`` result) under ``name``. Raises
        if it exposes no ``_cache_size`` (nothing to track)."""
        if not hasattr(fn, "_cache_size"):
            raise TypeError(
                f"{name}: object has no _cache_size(); pass the "
                "jax.jit-wrapped callable itself")
        try:
            ref = weakref.ref(fn)
        except TypeError:
            ref = lambda fn=fn: fn          # not weakref-able: strong
        with self._lock:
            self._watched[name] = {
                "ref": ref,
                # what the compile log calls it
                "program": getattr(fn, "__name__", name),
                "warm": None,                # cache size at mark_warm
                "warm_t": None,              # ... and the clock then
                "warned_at": None,           # size already warned about
                "last": None,                # last observed size (kept
            }                                # after the fn is GC'd)

    def mark_warm(self, name: Optional[str] = None) -> None:
        """Freeze the current cache size(s) as the expected steady
        state; growth past it is a recompile."""
        with self._lock:
            entries = ([self._watched[name]] if name is not None
                       else list(self._watched.values()))
            for e in entries:
                fn = e["ref"]()
                if fn is not None:
                    e["warm"] = self._cache_size(fn)
                    e["warm_t"] = now()

    def counts(self) -> Dict[str, int]:
        """Compile count per watched function — live cache size, or the
        last observed size once the function has been GC'd (a finished
        trainer's epoch program stays visible in the final snapshot)."""
        out = {}
        with self._lock:
            items = list(self._watched.items())
        for name, e in items:
            fn = e["ref"]()
            size = self._cache_size(fn) if fn is not None else None
            if size is not None:
                e["last"] = size
            if size is not None or e["last"] is not None:
                out[name] = size if size is not None else e["last"]
        return out

    def check(self, warn: bool = True) -> Dict[str, int]:
        """Poll watched functions; returns ``{name:
        recompiles_after_warm}`` for those that grew past their warm
        size (empty when all quiet). Updates the registry counters
        either way."""
        grew: Dict[str, int] = {}
        with self._lock:
            items = list(self._watched.items())
        gauge = self.registry.gauge("jit.compile_count")
        for name, e in items:
            fn = e["ref"]()
            if fn is None:
                continue
            size = self._cache_size(fn)
            if size is None:
                continue
            e["last"] = size
            gauge.set(size, fn=name)
            warm = e["warm"]
            if warm is None or size <= warm:
                continue
            grew[name] = size - warm
            if warn and e["warned_at"] != size:
                e["warned_at"] = size
                spent = "; ".join(
                    f"{c['program']} {c['seconds']:.3f} s"
                    f" ({c['cache'] or 'no'} cache) in "
                    f"{'/'.join(c['span']) or 'no span'}"
                    for c in self._after_warm(e))
                warnings.warn(
                    f"jitted function {name!r} recompiled after "
                    f"warm-up ({size - warm} new executable(s), cache "
                    f"size {warm} -> {size}"
                    f"{': ' + spent if spent else ''}) — a hot step "
                    "retracing usually means unstable shapes/dtypes "
                    "(shape leak)",
                    RecompileWarning, stacklevel=2)
        return grew

    @staticmethod
    def _after_warm(e) -> List[Dict]:
        """The compile log's entries of one watched function since its
        ``mark_warm``: ``{"program", "seconds", "cache", "span"}``."""
        if e["warm_t"] is None:
            return []
        return [{"program": c["program"],
                 "seconds": c["trace_s"] + c["lower_s"] + c["backend_s"],
                 "cache": c["cache"], "span": c["span"]}
                for c in compile_log()
                if c["program"] == e["program"]
                and c["t_end"] > e["warm_t"]]

    def after_warm(self) -> List[Dict]:
        """What the functions that grew past their warm size cost:
        their entries of the compile log since ``mark_warm``, oldest
        first (empty when all quiet, or once the log dropped them)."""
        return [cost for name in self.check(warn=False)
                for cost in self._after_warm(self._watched[name])]


def memory_watermark(registry=None):
    """Record per-device ``bytes_in_use`` gauges (watermark = ``max``
    across calls). Returns the stats list, or None where the backend
    exposes none (virtual CPU devices)."""
    from distkeras_tpu.obs import get_registry
    from distkeras_tpu.utils.profiling import device_memory_stats
    registry = registry if registry is not None else get_registry()
    stats = device_memory_stats()
    if not stats:
        return None
    gauge = registry.gauge("device.bytes_in_use")
    for s in stats:
        if s.get("bytes_in_use") is not None:
            gauge.set(s["bytes_in_use"], device=s["device"])
    return stats
