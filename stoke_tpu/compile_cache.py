"""Persistent AOT compilation cache (ISSUE 6 tentpole, cache half).

Warm-up XLA compilation of the step programs is pure ``goodput_compile_s``
paid on every restart of an identical job.  This module removes it with
three cooperating layers, all of which dispatch through ordinary
``jax.jit`` — donation, async dispatch, and numerics are byte-for-byte
the no-cache path:

1. **Process program cache** — an in-process map from HLO cache key to
   the first already-built jitted fn for that exact program.  A second
   facade in the same process whose step program lowers to identical HLO
   dispatches through the first facade's fn; jax's own per-function
   executable cache then serves every call with ZERO recompilation.
   Works on every backend.
2. **XLA persistent cache** — :func:`install_persistent_xla_cache`
   turns on the process-global jax compilation cache at
   :func:`persistent_cache_dir` (``JAX_COMPILATION_CACHE_DIR`` when the
   environment sets it, else one fixed directory in the checkout), so
   backend compiles are disk-memoized across processes and a warm
   process's compiles load in milliseconds.  NON-CPU backends only: this
   jaxlib's CPU persistent cache round-trips executables through a
   serialization path that corrupts the heap for sharded/donated step
   programs (reproducible ``malloc_consolidate()`` aborts driving the
   oss/sddp/fsdp equivalence suite under an active cache), so on CPU it
   is refused and warm starts are same-process only.
3. **AOT program ledger** — :class:`CompileCache` explicitly lowers each
   step program at first dispatch, keys it by a sha256 of the **lowered
   HLO text** plus an :func:`environment_fingerprint`, and keeps a
   provenance marker per key recording the cold first-dispatch seconds.
   A warm start (via layer 1 or 2) counts a ``compile_cache_hit`` and
   credits the recorded seconds as reclaimed — feeding the goodput
   ledger's ``compile_fresh`` vs ``compile_cached`` split.  On a miss
   the compiled executable is additionally serialized
   (``jax.experimental.serialize_executable``) next to the marker as an
   offline AOT artifact (``exe-<key>.bin``) when a live XLA cache can
   absorb the extra compile.

Why the step programs do NOT dispatch through deserialized executables:
on current jax, ``deserialize_and_load`` loses the donated-input
bookkeeping — an executable with input/output buffer aliasing hands back
outputs whose producers jax no longer tracks, and chaining such calls
over carried training state can consume an aliased buffer before the
previous step materialized it (observed as silent numeric corruption on
the CPU mesh; tests/test_compile_cache.py pins the safe architecture).
The CPU persistent-cache heap corruption above is the same bookkeeping
loss surfacing inside XLA itself.

Why key on the lowered HLO and not on config metadata: the HLO *is* the
program.  Any change in model code, loss math, optimizer hyperparameters
(baked in as constants), shapes, shardings, precision, or grad-accum
structure changes the text and therefore the key — a warm start can
never be served different math, and reclaimed-seconds credit can never
be claimed for it.  What the HLO does not capture — the compiler that
will run it — is the fingerprint's job: jax/jaxlib versions, backend,
``XLA_FLAGS``, device topology, process count.

Failure policy: every cache-layer failure (serialization unsupported,
corrupt entry, filesystem error) degrades to plain compilation with a
warning and a ``serialize_errors`` count — the cache must never be what
kills a training run.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
import warnings
from typing import Any, Dict, Optional

#: cache entry filename prefix (``<prefix><sha>.json`` marker +
#: optional ``.bin`` serialized-executable artifact)
ENTRY_PREFIX = "exe-"

#: where JAX's persistent cache goes when the environment does not place
#: it: one fixed directory at the root of the checkout, resolved from this
#: file — never from the cwd, a temporary name, a pid or the time, because
#: the path must be the same for every process that should share entries
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)

#: module-global: the persistent-XLA-cache directory this process
#: installed (the jax knob is process-global), None before
_xla_cache_installed: Optional[str] = None
_xla_cache_lock = threading.Lock()

#: process-level program cache: HLO cache key -> the first already-built
#: jitted fn for that exact program.  A SECOND facade in the same process
#: whose program lowers to the same HLO dispatches through the first
#: facade's jit fn — jax's own per-function executable cache then serves
#: every call with ZERO recompilation, and the semantics are plain
#: ``jax.jit`` (identical HLO => identical math; donation/async exactly
#: as ever).  This is the warm-start layer that works on EVERY backend —
#: including CPU, where both jax-level serialization paths are unsafe
#: (see install_persistent_xla_cache / the module docstring).
_process_fn_cache: Dict[str, Any] = {}
_process_fn_lock = threading.Lock()
#: cap: each cached fn keeps its closure (adapter/optimizer objects)
#: alive; a bounded map keeps pathological many-model processes from
#: retaining unbounded state.  Beyond the cap new programs simply stop
#: being shareable (never an error).
_PROCESS_FN_CAP = 256

#: one CPU-refusal warning per process (every CompileConfig construction
#: re-attempts the install; the refusal reason does not change)
_cpu_refusal_warned = False

#: per-run memo cap, mirroring the engine's _MAX_SHAPE_SIGS discipline:
#: each new (program, shape signature) pays a full trace+lower plus
#: marker I/O on its first dispatch, so under pathological shape churn
#: the ledger stops engaging beyond the cap (dispatch degrades to the
#: plain jitted fn; host memory stays bounded)
_MEMO_CAP = 1024


def environment_fingerprint(
    *,
    xla_flags: Optional[str] = None,
    jax_version: Optional[str] = None,
    jaxlib_version: Optional[str] = None,
    backend: Optional[str] = None,
    topology: Optional[str] = None,
    n_processes: Optional[int] = None,
) -> str:
    """Canonical description of the compiler + topology an entry was
    built for.  Two environments with different fingerprints must never
    share cache entries even for identical HLO: the same program
    compiles differently under a different jaxlib, flag set, or device
    assignment.

    All components are overridable for tests; defaults read the live
    process.  Deterministic across processes (no ``hash()``, no ids).
    """
    if jax_version is None or jaxlib_version is None or backend is None \
            or topology is None or n_processes is None:
        import jax
        import jaxlib

        if jax_version is None:
            jax_version = jax.__version__
        if jaxlib_version is None:
            jaxlib_version = jaxlib.__version__
        if backend is None:
            backend = jax.default_backend()
        if topology is None:
            devs = jax.devices()
            topology = f"{len(devs)}x{devs[0].device_kind}"
        if n_processes is None:
            n_processes = jax.process_count()
    if xla_flags is None:
        xla_flags = os.environ.get("XLA_FLAGS", "")
    return "|".join(
        (
            "stoke-compile-cache/v1",
            jax_version,
            jaxlib_version,
            backend,
            xla_flags,
            topology,
            str(int(n_processes)),
        )
    )


def hlo_cache_key(hlo_text: str, fingerprint: str) -> str:
    """Content-addressed cache key: sha256 over the lowered program body
    and the environment fingerprint.

    The module NAME is normalized out before hashing via the SHARED
    :func:`stoke_tpu.analysis.hlo_text.normalize_module_name` (the
    program auditor consumes the same normalizer — ISSUE 15: two
    normalizers would drift): it carries the jit wrapper's function name
    plus any per-process uniquifying counter, and a renamed module is
    still the same program.  Everything else, including the mhlo
    partition/replica attributes, stays in the hash.  Stable across
    processes (tested in tests/test_compile_cache.py).
    """
    from stoke_tpu.analysis.hlo_text import normalize_module_name

    body = normalize_module_name(hlo_text)
    h = hashlib.sha256()
    h.update(fingerprint.encode())
    h.update(b"\x00")
    h.update(body.encode())
    return ENTRY_PREFIX + h.hexdigest()[:40]


def persistent_cache_dir() -> str:
    """THE rule for where JAX's persistent compilation cache lives:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax reads
    it itself, and this package then sets no other), else
    :data:`DEFAULT_CACHE_DIR`.  ``chip_smoke.py`` and
    ``CompileConfig``'s default both resolve through here."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def install_persistent_xla_cache(
    min_compile_time_s: float = 0.0,
) -> Optional[str]:
    """Turn on jax's process-global persistent compilation cache at
    :func:`persistent_cache_dir`.  Idempotent.  Returns the directory, or
    None when the cache is refused (CPU backend).

    With ``JAX_COMPILATION_CACHE_DIR`` set jax already holds that path:
    nothing is re-pointed and the cache is not reset.  Without it the knob
    is set to the fixed in-checkout directory, and the cache is reset so a
    compile that ran before this call cannot have latched it off.

    REFUSED on the CPU backend: this jaxlib's CPU persistent cache
    round-trips executables through a serialization path that corrupts
    the heap for sharded/donated step programs (reproducible
    ``malloc_consolidate(): invalid chunk size`` aborts driving the
    oss/sddp/fsdp equivalence suite under an active cache) — the same
    bookkeeping loss that makes ``deserialize_and_load`` dispatch unsafe.
    CPU warm starts come from the process-level program cache instead.
    """
    global _xla_cache_installed, _cpu_refusal_warned
    import jax

    with _xla_cache_lock:
        if _xla_cache_installed is not None:
            return _xla_cache_installed
        if jax.default_backend() == "cpu":
            if not _cpu_refusal_warned:
                _cpu_refusal_warned = True
                warnings.warn(
                    "Stoke -- persistent XLA compilation cache disabled on "
                    "the CPU backend (its executable serialization corrupts "
                    "the heap for sharded/donated programs on this jaxlib); "
                    "same-process warm starts still hit the in-process "
                    "program cache"
                )
            return None
        cache_dir = persistent_cache_dir()
        os.makedirs(cache_dir, exist_ok=True)
        # cache every program, small ones too: a second run of the same
        # programs must find all of them, whatever each took to compile
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(min_compile_time_s),
        )
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            from jax.experimental.compilation_cache import (
                compilation_cache as _cc,
            )

            jax.config.update("jax_compilation_cache_dir", cache_dir)
            _cc.reset_cache()
        _xla_cache_installed = cache_dir
        return cache_dir


def active_xla_cache_dir() -> Optional[str]:
    """The directory this process's persistent cache was installed at
    (None when none).  Markers record it so a hit is only claimed when the
    cache that would serve the compile is the one the marker's entry was
    persisted into."""
    return _xla_cache_installed


def ledger_dir(cfg) -> str:
    """Directory of a ``CompileConfig``'s AOT marker ledger: its explicit
    ``cache_dir``, else ``aot/`` under :func:`persistent_cache_dir`."""
    return cfg.cache_dir or os.path.join(persistent_cache_dir(), "aot")


class CompileCache:
    """One per :class:`~stoke_tpu.facade.Stoke` run (constructed by the
    facade when a ``CompileConfig`` is supplied; the engine calls
    :meth:`executable` at each step-program dispatch site).

    Counters (registered in the run's telemetry registry, so they
    surface in snapshots / Prometheus and feed the goodput ledger's
    ``compile_fresh``/``compile_cached`` split):

    - ``compile_cache/hits_total`` / ``misses_total``: per-program AOT
      ledger lookups (a hit means the impending backend compile is
      served from the persistent cache).
    - ``compile_cache/hit_s_total``: first-dispatch wall seconds of hit
      programs — the *cached* warm-start cost actually paid (lowering +
      cache-served compile + first run).
    - ``compile_cache/saved_s_total``: the markers' recorded cold
      first-dispatch seconds — the reclaimed ``goodput_compile_s``.
    - ``compile_cache/serialize_errors_total``: artifact/marker
      degradations.
    """

    def __init__(self, cfg, registry=None):
        self.cfg = cfg
        self.registry = registry
        self.hits = 0
        self.misses = 0
        self.serialize_errors = 0
        self.saved_compile_s = 0.0
        self.fingerprint = environment_fingerprint()
        # per-run memo: (engine program key, shape signature) resolved ->
        # one ledger lookup per program signature per run; every later
        # dispatch is a dict lookup returning the jit fn untouched
        self._memo: Dict[Any, Any] = {}
        self._lock = threading.Lock()
        self._warned = False
        #: the AOT marker ledger's directory
        self.dir = ledger_dir(cfg)
        os.makedirs(self.dir, exist_ok=True)
        if cfg.xla_cache:
            install_persistent_xla_cache(cfg.min_compile_time_s)
        # hits require a LIVE persistent cache (ours or another run's in
        # this process — the knob is global): a marker alone reclaims
        # nothing, and counting it as a hit would be a lie
        self.xla_available = active_xla_cache_dir() is not None
        if registry is not None:
            registry.counter(
                "compile_cache/hits_total",
                help="AOT program-ledger hits (warm starts)",
            )
            registry.counter(
                "compile_cache/misses_total",
                help="AOT program-ledger misses (fresh compiles)",
            )
            registry.counter(
                "compile_cache/hit_s_total",
                help="ledger bookkeeping seconds booked on warm starts",
            )
            registry.counter(
                "compile_cache/saved_s_total",
                help="cold compile seconds reclaimed by cache hits",
            )
            registry.counter(
                "compile_cache/serialize_errors_total",
                help="cache marker/artifact degradations",
            )

    # ------------------------------------------------------------------ #
    # the engine-facing hook
    # ------------------------------------------------------------------ #

    def executable(self, program: str, memo_key, fn, args: tuple):
        """Resolve the callable for one dispatch of jitted ``fn`` at
        ``args``.  ALWAYS dispatches through a plain jitted fn
        (donation/async semantics untouched); the first call per
        ``memo_key`` lowers the program for its HLO key, checks the
        ledger, and resolves to either the process-cached already-built
        fn (warm hit — EVERY later dispatch of this signature goes
        through it too, or the hit would merely defer the recompile to
        the second dispatch) or a one-shot timing wrapper that records
        the cold first-dispatch cost as the marker's reclaimed seconds
        (miss).  Any cache failure degrades to ``fn`` untouched.
        """
        entry = self._memo.get(memo_key)
        if entry is not None:
            return entry
        with self._lock:
            entry = self._memo.get(memo_key)
            if entry is not None:
                return entry
            if len(self._memo) >= _MEMO_CAP:
                # pathological shape churn: beyond the cap new
                # signatures skip the ledger entirely (no lower, no
                # marker I/O, no memo growth) — never an error
                return fn
            if not self.cfg.aot:
                self._memo[memo_key] = fn
                return fn
            try:
                first, steady = self._first_dispatch(program, fn, args)
            except Exception as e:
                self._note_error(program, e)
                first = steady = fn
            # later dispatches of this signature bypass the ledger —
            # dispatching through the RESOLVED fn (the shared one on a
            # process-cache hit)
            self._memo[memo_key] = steady
            return first

    def _first_dispatch(self, program: str, fn, args: tuple):
        """Resolve one program's first dispatch.  Returns ``(first,
        steady)``: the callable for THIS dispatch and the one every
        later dispatch of the same signature memoizes."""
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        key = hlo_cache_key(lowered.as_text(), self.fingerprint)
        base = os.path.join(self.dir, key)
        # hit accounting starts AFTER lowering: tracing/lowering happens
        # on the cold path too and is counted in neither path's compile
        # bucket — the hit seconds measure only the ledger's own
        # bookkeeping, keeping cold-vs-warm goodput_compile_s symmetric
        t_ledger = time.perf_counter()
        meta = self._read_marker(base)
        # layer A — process program cache: a facade in THIS process
        # already built the identical program; dispatch through its jit
        # fn (already compiled, plain jit semantics) — zero recompile on
        # any backend
        with _process_fn_lock:
            shared = _process_fn_cache.get(key)
        if shared is not None:
            self._book_hit(meta, t_ledger)
            return shared, shared
        # layer B — persistent XLA cache (non-CPU backends): the marker
        # proves this exact program's compile was persisted, and only
        # when the LIVE cache is the one it was persisted into — markers
        # pointing at a different (or no) XLA cache dir would claim
        # reclaimed seconds while the backend compile runs full codegen
        if (
            meta is not None
            and self.xla_available
            and meta.get("xla_cache_dir") == active_xla_cache_dir()
        ):
            self._book_hit(meta, t_ledger)
            self._publish(key, fn)
            return fn, fn
        self.misses += 1
        self._inc("compile_cache/misses_total")

        def first_call_miss(*a):
            out = fn(*a)
            # the marker's cold cost: lowering + XLA compile + first run
            # (compile-dominated for real step programs) — what a warm
            # start reclaims
            self._write_marker(
                base, program, time.perf_counter() - t0, lowered
            )
            self._publish(key, fn)
            return out

        return first_call_miss, fn

    def _book_hit(self, meta: Optional[Dict[str, Any]], t0: float) -> None:
        """Account one warm start: the hit count, the reclaimed seconds
        the marker recorded, and the ledger's own bookkeeping seconds
        (marker read + lookup — measured after lowering and before
        dispatch, so neither tracing nor step execution ever lands in
        the compile accounting)."""
        self.hits += 1
        self._inc("compile_cache/hits_total")
        self._inc("compile_cache/hit_s_total", time.perf_counter() - t0)
        if meta is not None:
            saved = float(meta.get("compile_time_s", 0.0))
            self.saved_compile_s += saved
            self._inc("compile_cache/saved_s_total", saved)

    @staticmethod
    def _publish(key: str, fn) -> None:
        with _process_fn_lock:
            if len(_process_fn_cache) < _PROCESS_FN_CAP:
                _process_fn_cache.setdefault(key, fn)

    # ------------------------------------------------------------------ #
    # ledger entries
    # ------------------------------------------------------------------ #

    def _read_marker(self, base: str) -> Optional[Dict[str, Any]]:
        try:
            with open(base + ".json") as f:
                return json.load(f)
        except OSError:
            return None
        except ValueError as e:  # corrupt marker: a miss, not a crash
            self._note_error("marker", e, what="read")
            return None

    def _write_marker(self, base: str, program: str, cold_s: float,
                      lowered) -> None:
        """Persist the provenance marker (atomic tmp + rename, pid-unique
        so processes racing on the same content-addressed entry cannot
        torn-write) and — best effort — the serialized executable
        artifact for offline AOT use."""
        try:
            meta = {
                "program": program,
                "compile_time_s": round(cold_s, 6),
                "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
                "fingerprint": self.fingerprint,
                # the persistent cache this compile landed in — a later
                # run only claims a hit when the SAME cache will serve it
                "xla_cache_dir": active_xla_cache_dir(),
            }
            tmp = f"{base}.json.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(meta, f, indent=2)
            os.replace(tmp, base + ".json")
        except Exception as e:
            self._note_error(program, e, what="marker write")
            return
        if not self.cfg.serialize_executables:
            return
        try:
            from jax.experimental.serialize_executable import serialize

            # the jit call just populated the persistent cache, so this
            # extra compile is served from disk (cheap); without a live
            # cache — or when the compile fell below the persistence
            # threshold and was therefore NOT cached (cold_s bounds the
            # compile time from above) — it would re-run full codegen
            # and double the cold start — skip
            if not self.xla_available:
                return
            if (
                self.cfg.min_compile_time_s > 0
                and cold_s < self.cfg.min_compile_time_s
            ):
                return
            payload, in_tree, out_tree = serialize(lowered.compile())
            tmp = f"{base}.bin.{os.getpid()}.tmp"
            with open(tmp, "wb") as f:
                pickle.dump((payload, in_tree, out_tree), f)
            os.replace(tmp, base + ".bin")
        except Exception as e:
            self._note_error(program, e, what="artifact serialize")

    def deserialize(self, key: str):
        """Load a serialized executable artifact for OFFLINE one-shot
        use (inspection, export, replay with ready inputs).  Do NOT
        drive a training loop's carried state through the result: on
        current jax a deserialized executable loses donated-input
        bookkeeping, and chaining calls over aliased state buffers races
        their producers (the module docstring pins the evidence).
        Loadability is backend-dependent — the CPU backend cannot always
        reload executables whose compile was itself served from the
        persistent cache ("Symbols not found"); callers must treat a
        raising deserialize as "artifact unusable on this backend"."""
        from jax.experimental.serialize_executable import (
            deserialize_and_load,
        )

        with open(os.path.join(self.dir, key + ".bin"), "rb") as f:
            payload, in_tree, out_tree = pickle.load(f)
        return deserialize_and_load(payload, in_tree, out_tree)

    # ------------------------------------------------------------------ #
    # bookkeeping
    # ------------------------------------------------------------------ #

    def _inc(self, name: str, value: float = 1.0) -> None:
        if self.registry is not None:
            self.registry.counter(name).inc(value)

    def _note_error(self, program: str, err, what: str = "cache") -> None:
        self.serialize_errors += 1
        self._inc("compile_cache/serialize_errors_total")
        if not self._warned:
            self._warned = True
            warnings.warn(
                f"Stoke -- compile cache {what} failed for program "
                f"{program!r}: {err!r}; degrading to plain compilation "
                f"(warned once per run)"
            )

    def stats(self) -> Dict[str, Any]:
        """Run-level cache accounting (also the ``Stoke.compile_cache``
        surface)."""
        return {
            "hits": self.hits,
            "misses": self.misses,
            "saved_compile_s": round(self.saved_compile_s, 6),
            "serialize_errors": self.serialize_errors,
            "cache_dir": self.dir,
            "xla_cache_active": self.xla_available,
            "entries": len(self._memo),
        }
