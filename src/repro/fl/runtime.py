"""Bucketed program runtime: one compile cache under every fused engine.

Every fused program in the FL stack — full/subset/wave cohort rounds
(``fl.cohort``), the batch-index sampler, pool staging, and the
fleet-GAN train/synthesis programs (``fl.fleetgan``) — compiles and
executes through one :class:`ProgramRuntime`. The runtime owns three
things the engines used to re-implement ad hoc:

**AOT compilation + accounting.** Programs are compiled ahead of time
(``jax.jit(fn, donate_argnums=...).lower(*args).compile()``) and the
resulting executables are cached by ``(kind, static config, donation
signature, argument shapes/dtypes/shardings)`` and then *called
directly*, so the
executable cache is the execution path (no separate jit call-path cache
to re-warm). Wall-clock spent compiling is charged per ``kind`` on cache
misses only; ``stats()``/``n_compiles``/``compile_time_s`` give the
unified breakdown that ``History.meta`` reports instead of the three
ad-hoc timers the engines used to keep.

**Shape bucketing.** A shape-diverse workload must not pay one compile
per shape variant:

- *Cohort widths* (:func:`bucket_width`): a subset round or async wave
  over K of N clients runs at width ``B = min(N, max(4, next_pow2(K)))``
  — padded rows gather a valid client's staged pool but carry **zero
  aggregation weight** (the in-program FedAvg weight vector is
  renormalized over the true selection with zeros in the pad tail), pad
  batch indices are drawn *outside* the program at the true K (threefry
  draws are not shape-stable, so padding must never touch the sample
  stream) and zero-filled, and per-client metrics are sliced back to the
  true K on the host. A participation sweep over K ∈ {2,…,N} therefore
  compiles O(log N) programs instead of O(N), and padding never leaks
  into sampling, aggregation, or uplink-byte accounting.
- *Batch buckets with mean-correction* (``gan.train_step_bucketed``):
  GAN minibatch losses are batch means, so the fleet engine pads every
  client's minibatch to one shared bucket and computes **masked means**
  (``sum(per_row * mask) / n_true`` — the batch-mean loss rescaled by
  true-batch/padded-batch), which zeroes every padded row's gradient
  contribution exactly; all batch-size groups then share one train
  compile. Per-step noise is pre-drawn at the true batch shape
  (``gan.gan_z_stream``) so the RNG stream stays bitwise the sequential
  one.
- *Row buckets* (:func:`bucket_rows`): chunked staging / synthesis row
  counts pad to power-of-two buckets so ragged tails reuse a compile.

**Non-blocking dispatch.** ``dispatch()`` returns a :class:`Handle`
wrapping the executable's output arrays without forcing a host sync —
under JAX's asynchronous dispatch the program runs while the caller
stages other work; ``Handle.result()`` blocks and materializes. The
fleet-GAN synthesis dispatch uses this directly, and
``fleetgan.FleetGANJob`` (launch/resolve) is the engine-level form of
the same pattern: the simulator launches GAN prep, the cohort engine
stages the CLIP pools while those programs run, then resolves. Since
the pipelined round loop (PR 10) the handle is dependency-tracked:
``dispatch()`` accepts other handles as arguments (their outputs are
consumed without materializing), and a dispatch that *donates* buffers
registers a donation hazard on them — any later runtime call consuming
a donated-in-flight buffer raises loudly instead of reading freed
memory, until the donating handle materializes (after which JAX's own
deleted-array error still fires).

**Host-sync tracing.** Every intentional materialization point in the
stack — ``Handle.result()``, ``ProgramRuntime.sync()``, the
simulator's metric-ring flushes — counts into the module-level
``SYNC_TRACES`` ledger (the ``KERNEL_TRACES`` pattern), so tests and
the CI smoke can assert a pipelined steady-state round performs zero
host syncs rather than silently degenerating to the serial loop.

**Names in a profile.** Every program runs inside a
``jax.named_scope`` named by its kind, so each device op of a profiled
run carries the kind in its op name (``jit(round_fn)/full_round/...``).
On the host the runtime opens ``jax.profiler.TraceAnnotation`` spans at
the points its counters already mark: ``runtime.compile:<kind>`` on a
cache miss, ``runtime.dispatch:<kind>`` around each dispatch, and
``runtime.sync:<tag>`` where ``SYNC_TRACES`` counts a host sync. They
land on the device trace's clock and cost nothing while no profile is
being taken.
"""
from __future__ import annotations

import functools
import time
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

# Host-sync trace ledger (the KERNEL_TRACES pattern from kernels.ops):
# counts *intentional materialization points* by tag, incremented at
# the moment the host blocks on device results. Pipelined-mode tests
# reset it, run R rounds, and assert the steady-state tags stayed 0.
SYNC_TRACES: Dict[str, int] = {}


def sync_count(tag: str, n: int = 1) -> None:
    """Charge ``n`` host-sync events to ``tag`` in ``SYNC_TRACES``."""
    SYNC_TRACES[tag] = SYNC_TRACES.get(tag, 0) + int(n)


def reset_sync_traces() -> None:
    SYNC_TRACES.clear()


def span(name: str):
    """A host span in the profiler's trace (free while none is taken)."""
    return jax.profiler.TraceAnnotation(name)


def scoped(kind: str, fn: Callable) -> Callable:
    """``fn`` traced inside ``jax.named_scope(kind)``, keeping its name
    (and so the compiled module's name)."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.named_scope(kind):
            return fn(*args, **kwargs)
    return run

# Cohort-width buckets below this floor are not worth separate programs:
# a width-4 program over a width-2 selection wastes two masked rows of a
# cheap round, while halving the number of compiles a K-sweep pays.
MIN_COHORT_BUCKET = 4


def pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    if n < 1:
        raise ValueError(f"pow2_ceil needs n >= 1, got {n}")
    return 1 << (int(n) - 1).bit_length()


def shard_multiple(n: int, shards: int) -> int:
    """Smallest multiple of ``shards`` >= n."""
    if shards < 1:
        raise ValueError(f"shard_multiple needs shards >= 1, got {shards}")
    return -(-int(n) // int(shards)) * int(shards)


def bucket_width(k: int, n: int, *, min_bucket: int = MIN_COHORT_BUCKET,
                 shards: int = 1) -> int:
    """Cohort-axis bucket for a selection of ``k`` out of ``n`` clients:
    the next power of two (floored at ``min_bucket``), clamped to ``n``.
    ``k == n`` always maps to ``n`` itself, so full-cohort selections
    never pad — the K=N subset round stays bit-identical to the
    gather-free full round.

    ``shards`` composes the bucket with mesh sharding of the cohort
    axis: the width rounds up to a shard multiple (still clamped to
    ``n``) so every data-parallel shard holds the same number of rows.
    The extra rows follow the existing pad contract — they gather a
    valid client's staged pool but carry zero aggregation weight and
    exactly-zero gradient/partial-sum contribution — so a sharded
    selection never needs its own padding rule. A mesh-sharded
    population must already satisfy ``n % shards == 0`` (the cohort
    engine enforces it), which keeps the K=N clamp a shard multiple
    too."""
    if not 1 <= k <= n:
        raise ValueError(f"selection width {k} out of range for {n}")
    if shards > 1 and n % shards:
        raise ValueError(
            f"population {n} not divisible by {shards} mesh shards — "
            "the staged cohort axis cannot shard evenly")
    if k >= n:
        return n
    b = min(n, max(min_bucket, pow2_ceil(k)))
    if shards > 1:
        b = min(n, shard_multiple(b, shards))
    return b


def bucket_rows(n: int, cap: int) -> int:
    """Row-count bucket for chunked row-wise programs (staging encode,
    GAN synthesis): the next power of two, clamped to ``cap``."""
    if n < 1:
        raise ValueError(f"bucket_rows needs n >= 1, got {n}")
    return min(int(cap), pow2_ceil(n))


def pad_leading(arr, width: int, fill=0):
    """Zero-(or ``fill``-)pad ``arr`` along axis 0 to ``width`` rows."""
    n = arr.shape[0]
    if n == width:
        return arr
    if n > width:
        raise ValueError(f"cannot pad {n} rows down to {width}")
    pad = jnp.full((width - n,) + tuple(arr.shape[1:]), fill, arr.dtype)
    return jnp.concatenate([arr, pad])


class Handle:
    """Dependency-tracked, non-blocking view of a dispatched program's
    outputs. The wrapped arrays are live as soon as the dispatch returns
    (JAX async dispatch); ``result()`` blocks until the computation
    finishes, counts the sync in ``SYNC_TRACES`` (tags ``handle_wait``
    and ``handle_wait:<kind>``), clears any donation hazards this
    dispatch registered, and returns the output tree.

    ``deps`` records the handles whose outputs fed this dispatch
    (``ProgramRuntime.dispatch`` unwraps handle arguments), so a
    pipeline's dataflow is inspectable without materializing anything.
    A handle whose dispatch *donated* input buffers blocks reuse of
    those buffers — the owning runtime raises on any later call that
    consumes them — until ``result()`` materializes the outputs."""

    __slots__ = ("kind", "deps", "_out", "_done", "_runtime",
                 "_hazard_ids")

    def __init__(self, out, *, kind: str = "anon", deps: Tuple = (),
                 runtime=None, hazard_ids: Tuple[int, ...] = ()):
        self.kind = kind
        self.deps = tuple(deps)
        self._out = out
        self._done = False
        self._runtime = runtime
        self._hazard_ids = tuple(hazard_ids)

    @property
    def done(self) -> bool:
        """True once ``result()`` has materialized the outputs."""
        return self._done

    def result(self):
        if not self._done:
            sync_count("handle_wait")
            sync_count(f"handle_wait:{self.kind}")
            with span(f"runtime.sync:handle_wait:{self.kind}"):
                jax.block_until_ready(jax.tree.leaves(self._out))
            self._done = True
            if self._runtime is not None and self._hazard_ids:
                self._runtime._clear_hazards(self._hazard_ids)
        return self._out

    @property
    def out(self):
        """The (possibly still-computing) output tree."""
        return self._out


class ProgramRuntime:
    """One AOT-compile cache + accounting ledger for a family of fused
    programs. Engines share a runtime (the simulator builds one per run
    and threads it through the cohort engine and the fleet-GAN engine)
    so ``History.meta`` reports a single unified compile breakdown, and
    identical programs built by different engines (e.g. a benchmark
    sweeping cohort widths over one staged population) share compiles.

    ``max_entries`` bounds the executable cache with LRU eviction (0 =
    unbounded, the default): long chaos sweeps touch many width/step-
    profile buckets, and without a bound every one stays pinned for the
    process lifetime. An evicted program recompiles (and recharges the
    ledger) on next use; eviction counts land per kind in ``stats()``
    (``n_evicted``) and in total via ``n_evictions``, so a sweep whose
    bound is set too tight shows up in the compile ledger instead of as
    silent thrash.
    """

    def __init__(self, max_entries: int = 0):
        if max_entries < 0:
            raise ValueError(f"max_entries={max_entries} must be >= 0 "
                             "(0 disables eviction)")
        self.max_entries = int(max_entries)
        self._exes: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._kinds: Dict[str, Dict[str, float]] = {}
        # donation hazards: id(leaf) -> (weakref to the donated leaf,
        # donating program kind). Registered by dispatch() on donated
        # argument leaves, cleared when the donating handle materializes
        # or the leaf is garbage-collected (dead refs are pruned lazily).
        self._hazards: Dict[int, Tuple[Any, str]] = {}

    # -- cache ---------------------------------------------------------
    @staticmethod
    def _shard_sig(leaf) -> Tuple:
        """Sharding identity of one argument leaf. AOT executables bake
        their input shardings in at ``lower()`` time, so a sharded and
        an unsharded program over identical shapes are *different
        programs* and must never collide in the cache. Plain host
        arrays and single-device placements (the overwhelmingly common
        case) all map to ``()`` so the pre-mesh cache behavior — and
        its compile counts — are unchanged; only genuinely
        mesh-partitioned inputs (NamedSharding, or anything spanning
        more than one device) contribute a key."""
        s = getattr(leaf, "sharding", None)
        if s is None:
            return ()
        try:
            from jax.sharding import NamedSharding
            if isinstance(s, NamedSharding):
                mesh = s.mesh
                return (tuple(mesh.axis_names),
                        tuple(int(mesh.shape[a]) for a in mesh.axis_names),
                        str(s.spec))
            if len(s.device_set) > 1:
                return (str(s),)
        except Exception:
            return ()
        return ()

    @classmethod
    def _sig(cls, args) -> Tuple:
        return tuple(
            (tuple(getattr(l, "shape", ())),
             str(getattr(l, "dtype", type(l).__name__)),
             cls._shard_sig(l))
            for l in jax.tree.leaves(args))

    def compile(self, kind: str, build: Callable[[], Callable], args,
                *, static_key: Tuple = (),
                donate_argnums: Sequence[int] = ()):
        """Return the compiled executable for ``build()`` at ``args``'
        shapes, compiling (and charging wall-clock to ``kind``) only on a
        cache miss. ``static_key`` must capture everything the program
        closes over that is not visible in the argument shapes."""
        donate = tuple(donate_argnums)
        key = (kind, static_key, donate, self._sig(args))
        exe = self._exes.get(key)
        if exe is None:
            t0 = time.perf_counter()
            with span(f"runtime.compile:{kind}"):
                exe = jax.jit(scoped(kind, build()),
                              donate_argnums=donate).lower(*args).compile()
            dt = time.perf_counter() - t0
            self._exes[key] = exe
            k = self._kinds.setdefault(
                kind, {"n_compiles": 0, "compile_time_s": 0.0})
            k["n_compiles"] += 1
            k["compile_time_s"] += dt
            while self.max_entries and \
                    len(self._exes) > self.max_entries:
                old_key, _ = self._exes.popitem(last=False)
                ok = self._kinds.setdefault(
                    old_key[0],
                    {"n_compiles": 0, "compile_time_s": 0.0})
                ok["n_evicted"] = int(ok.get("n_evicted", 0)) + 1
        else:
            self._exes.move_to_end(key)
        return exe

    # -- donation hazards ----------------------------------------------
    def _prune_hazards(self) -> None:
        dead = [i for i, (ref, _) in self._hazards.items()
                if ref() is None]
        for i in dead:
            del self._hazards[i]

    def _clear_hazards(self, ids: Sequence[int]) -> None:
        for i in ids:
            self._hazards.pop(i, None)

    def _check_hazards(self, args, kind: str) -> None:
        """Raise loudly if any argument leaf was donated by a dispatch
        that has not materialized yet — consuming it would read a buffer
        the backend may already have aliased for the donor's outputs."""
        if not self._hazards:
            return
        for leaf in jax.tree.leaves(args):
            ent = self._hazards.get(id(leaf))
            if ent is not None and ent[0]() is leaf:
                raise RuntimeError(
                    f"donation hazard: program {kind!r} consumes a "
                    f"buffer donated to in-flight program {ent[1]!r}; "
                    "materialize that handle (Handle.result()) before "
                    "reusing its donated inputs")

    def _register_hazards(self, args, donate, kind: str):
        ids = []
        for i in donate:
            for leaf in jax.tree.leaves(args[i]):
                try:
                    ref = weakref.ref(leaf)
                except TypeError:
                    continue
                self._hazards[id(leaf)] = (ref, kind)
                ids.append(id(leaf))
        return tuple(ids)

    def run(self, kind: str, build, args, **kw):
        """Compile-or-hit, then execute without forcing a host sync —
        the handle-free form of ``dispatch`` (same hazard checks and
        donation tracking), returning the raw output tree."""
        return self.dispatch(kind, build, args, **kw).out

    def count(self, kind: str, counter: str, n: int = 1) -> None:
        """Charge ``n`` to an auxiliary per-kind counter in the same
        ledger the compile accounting lives in — the serving plane's
        adapter cache reports hits/misses/evictions this way, so
        ``stats()`` (and therefore ``History.meta``) stays the one place
        every runtime-level count is read from."""
        k = self._kinds.setdefault(
            kind, {"n_compiles": 0, "compile_time_s": 0.0})
        k[counter] = int(k.get(counter, 0)) + int(n)

    def charge(self, kind: str, seconds: float, n: int = 1) -> None:
        """Charge ``seconds`` of compile-class wall-clock (and ``n``
        compile events) to ``kind`` directly — the kernel autotuner
        (``kernels.autotune``) books its block-shape sweep time here, so
        tuning cost appears in the same ``stats()`` breakdown as AOT
        compile cost instead of in a side ledger."""
        k = self._kinds.setdefault(
            kind, {"n_compiles": 0, "compile_time_s": 0.0})
        k["n_compiles"] += int(n)
        k["compile_time_s"] += float(seconds)

    def dispatch(self, kind: str, build, args, **kw) -> Handle:
        """Compile-or-hit, then execute without forcing a host sync,
        returning a dependency-tracked :class:`Handle`. Top-level
        positional arguments may themselves be handles — their output
        trees are consumed in place (no materialization) and recorded
        as dependencies. Donated argument buffers are registered as
        hazards until the returned handle materializes."""
        deps = tuple(a for a in args if isinstance(a, Handle))
        if deps:
            args = tuple(a.out if isinstance(a, Handle) else a
                         for a in args)
        self._prune_hazards()
        self._check_hazards(args, kind)
        with span(f"runtime.dispatch:{kind}"):
            out = self.compile(kind, build, args, **kw)(*args)
        donate = tuple(kw.get("donate_argnums", ()))
        hazard_ids = self._register_hazards(args, donate, kind) \
            if donate else ()
        return Handle(out, kind=kind, deps=deps, runtime=self,
                      hazard_ids=hazard_ids)

    def sync(self, tree, tag: str = "sync"):
        """Materialize a pytree of device arrays in bulk, charging one
        host-sync event to ``tag`` — the counted form every deliberate
        blocking point in the pipelined loop goes through. Non-array
        leaves pass through untouched."""
        sync_count(tag)
        with span(f"runtime.sync:{tag}"):
            jax.block_until_ready([
                l for l in jax.tree.leaves(tree)
                if hasattr(l, "block_until_ready")])
        return tree

    def clear(self):
        """Drop every cached executable and reset the accounting — used
        by long-lived shape sweeps to bound memory and by benchmarks to
        force a cold compile measurement."""
        self._exes.clear()
        self._kinds.clear()

    # -- accounting ----------------------------------------------------
    def stats(self) -> Dict[str, Dict[str, float]]:
        """Per-kind ``{"n_compiles", "compile_time_s"}`` breakdown."""
        return {k: dict(v) for k, v in self._kinds.items()}

    @property
    def n_compiles(self) -> int:
        return sum(int(v["n_compiles"]) for v in self._kinds.values())

    @property
    def compile_time_s(self) -> float:
        return sum(v["compile_time_s"] for v in self._kinds.values())

    @property
    def n_evictions(self) -> int:
        """Total LRU evictions (0 while the cache is unbounded)."""
        return sum(int(v.get("n_evicted", 0))
                   for v in self._kinds.values())

    def subtotal(self, prefix: str) -> Tuple[int, float]:
        """(n_compiles, compile_time_s) summed over kinds matching
        ``prefix`` — e.g. ``subtotal("gan_")`` for the GAN engine's share
        of the one cache."""
        n, t = 0, 0.0
        for k, v in self._kinds.items():
            if k.startswith(prefix):
                n += int(v["n_compiles"])
                t += v["compile_time_s"]
        return n, t
