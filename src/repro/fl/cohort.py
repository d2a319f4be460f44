"""Batched cohort execution engine: vmap/scan-fused federated rounds.

The sequential simulator runs each round as a Python loop over clients
with one jitted step per local batch — O(n_clients * local_steps) device
dispatches plus a host->device transfer per step. But with a frozen,
shared backbone and a tiny trainable tree, every client's local training
is the *same program over different data and trainable state*, which is
exactly the shape ``jax.vmap`` (over the cohort) + ``jax.lax.scan`` (over
local steps) compile into one fused device program.

This engine therefore executes an entire federated round — local Adam
training for every selected client, delta computation, per-client uplink
quantization, and weighted FedAvg aggregation — as **one jitted,
buffer-donated call**:

 - client trainables are stacked along a leading cohort axis (every
   client starts a round from the global trainables, so the stack is a
   broadcast);
 - each client's (GAN-rebalanced) data pool is staged on device once,
   zero-padded to a fixed shape (n_clients, P, ...) so shapes never
   recompile — and staging hoists every trainable-independent prefix of
   the forward to a one-time cost: pools are stored as pooled backbone
   features (adapter-only arms) or embedded patch tokens (LoRA arms),
   so local steps never re-run frozen computation the sequential
   interpreter redoes per batch;
 - per-step batch indices are drawn with ``jax.random`` in one small
   dedicated dispatch per round on replicated inputs (padding rows are
   never sampled: indices live in [0, pool_len)) and fed to the fused
   round as data, keeping the draw independent of the mesh layout;
 - uplink compression reuses the exact blockwise layout of the
   sequential path (quantization blocks run along trailing dims, so the
   stacked quantization is elementwise-identical to quantizing each
   client's delta separately);
 - with a mesh, the staged cohort arrays (and the per-round cohort-axis
   inputs) are sharded over the data-parallel axes
   (``launch.mesh.cohort_sharding``) and the vmapped local training
   runs under ``shard_map``, each device on its own cohort rows;
   aggregation then runs hierarchically
   (``server.aggregate_tree``): each shard reduces its own cohort rows
   to a partial weighted sum + partial mass, and only the small
   (shards, ...) partials cross the mesh in the global reduce.

The sequential ``Client.local_train`` path stays alive as the reference
oracle; ``round_indices`` reproduces the engine's sample sequence so
parity tests can drive both paths with identical batches.

Partial participation (``fl.sched``) builds on the same staging: the
pools of *all* clients stay device-resident, and a subset round is the
same fused program prefixed with a gather — ``pool_staged[sel]`` — so
selecting a different subset each round never re-uploads data.
``run_subset_round`` aggregates in-program (sync-partial); ``run_wave``
stops before aggregation and returns the stacked quantized deltas,
which the async scheduler buffers on the host and commits with
staleness-discounted weights. Heterogeneous per-client local-step
counts (availability traces) run inside the same fixed-length scan via
the ``active`` mask of ``optim.adam_scan`` — a masked step is a bitwise
no-op on (params, opt state).

Every fused program compiles and executes through the shared
:class:`repro.fl.runtime.ProgramRuntime` (AOT ``lower().compile()``,
one cache, per-kind compile accounting), and subset/wave cohort widths
are padded to power-of-two buckets (``runtime.bucket_width``): a
selection of K clients runs at width ``B >= K`` with pad rows that
gather client 0's staged pool, receive zero-filled batch indices (the
true K rows keep the exact ``round_indices`` sample stream — indices
are drawn outside the program at the true width), and carry zero
aggregation weight, so padding never leaks into sampling, aggregation,
or uplink accounting while a K-sweep compiles O(log N) programs instead
of O(N). On a mesh the bucket additionally rounds up to a shard
multiple (``bucket_width(..., shards=...)``) so the bucketed cohort
axis always splits evenly over the data-parallel shards. K=N never pads
(``bucket_width(N, N) == N``), keeping the degenerate full-sync case
bit-identical to the gather-free full round.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import clip as clip_lib
from repro.core import lora as lora_lib
from repro.core import losses, optim, quant
from repro.core.quant import tree_bytes
from repro.data.synthetic import stage_client_pools
from repro.fl import client as client_lib
from repro.fl import runtime as runtime_lib
from repro.fl import server
from repro.fl import strategies as strategies_lib
from repro.fl.strategies import Strategy
from repro.launch import mesh as mesh_lib


@dataclass(frozen=True)
class CohortConfig:
    """Static round-execution parameters (baked into the jitted round)."""
    strategy: Strategy
    local_steps: int
    batch_size: int
    lr: float
    mesh: Any = None          # optional Mesh: shard cohort over dp axes
    donate: bool = True       # donate the global-trainable buffers
    # stage the masked (heterogeneous-step) programs even when every
    # client's trace multiplier is 1 — the chaos layer cuts step counts
    # per client at dispatch time, which is just a heterogeneous step
    # profile the engine must be staged to honor
    force_het: bool = False


def encode_rows(frozen, ccfg, *, use_lora: bool, rows, runtime=None,
                chunk: int = 512):
    """Encode ``(n, H, W, ch)`` image rows through the
    trainable-independent prefix of the forward — the whole frozen
    backbone (pooled features) for adapter-only arms, the patch
    embedding (tokens) for LoRA arms — in fixed-size chunks through the
    shared program runtime. Full chunks run at ``chunk`` rows; the
    ragged tail pads to its power-of-two bucket, so any row count
    reuses O(log chunk) compiles while the pad waste stays below the
    tail itself (never a full chunk)."""
    runtime = runtime or runtime_lib.ProgramRuntime()
    n = rows.shape[0]
    flat = jnp.asarray(rows)

    def build():
        if use_lora:
            return lambda fz, x: clip_lib.embed_patches(fz, ccfg, x)
        return lambda fz, x: clip_lib.encode_image(fz, ccfg, x)

    def encode(piece):
        args = (frozen, piece)
        return runtime.compile("stage_encode", build, args,
                               static_key=(ccfg, use_lora))(*args)

    out = [encode(flat[i:i + chunk])
           for i in range(0, n - n % chunk, chunk)]
    tail = n % chunk
    if tail:
        ck = runtime_lib.bucket_rows(tail, chunk)
        out.append(encode(runtime_lib.pad_leading(
            flat[n - tail:], ck))[:tail])
    return jnp.concatenate(out) if len(out) != 1 else out[0][:n]


def stage_encoded_pools(frozen, ccfg, *, use_lora: bool, imgs, put=None,
                        chunk: int = 512, runtime=None):
    """Encode padded client pools ``(C, P, H, W, ch)`` via
    :func:`encode_rows` and reshape back to the cohort layout.

    This is the single staging pipeline for every pool that enters the
    cohort engine: raw client data and the fleet-GAN rebalancing sets
    (``fl.fleetgan``) flow through it identically, so GAN-augmented
    pools cost one staging pass like any other pool."""
    put = jnp.asarray if put is None else put
    C, P = imgs.shape[:2]
    staged = encode_rows(
        frozen, ccfg, use_lora=use_lora,
        rows=jnp.asarray(imgs).reshape(C * P, *imgs.shape[2:]),
        runtime=runtime, chunk=chunk)
    return put(staged.reshape(C, P, *staged.shape[1:]))


def sample_batch_indices(key, lens, steps: int, batch: int):
    """(n_clients, steps, batch) pool indices, client i's in
    [0, lens[i]). The engine draws these in a dedicated small dispatch on
    *replicated* inputs — never inside the sharded round program, where
    non-partitionable threefry would make the draw depend on the mesh
    layout — so ``round_indices`` (the eager form driving the sequential
    oracle) reproduces the engine's batches exactly on any mesh."""
    keys = jax.random.split(key, lens.shape[0])
    return jax.vmap(
        lambda k, n: jax.random.randint(k, (steps, batch), 0, n))(
            keys, lens)


def round_indices(key, lens, steps: int, batch: int) -> np.ndarray:
    """Host-side view of one round's per-client batch indices. For subset
    rounds pass ``lens[sel]`` (and the engine's ``max_steps``) — the
    fused program and the sequential oracle then see identical batches."""
    return np.asarray(sample_batch_indices(
        key, jnp.asarray(lens, jnp.int32), steps, batch))


def client_logits(frozen, ccfg, trainable, x, class_emb, *,
                  use_lora: bool):
    """One client's forward from its *staged* input to zero-shot class
    logits: ``x`` is the hoisted trainable-independent prefix output —
    pooled backbone features for adapter-only arms, embedded patch
    tokens for LoRA arms (see :func:`encode_rows`).

    This is the single stacked-adapter apply path: the cohort training
    loss vmaps it over the cohort axis, and the serving plane
    (``fl.serve``) vmaps it over the request axis (its quantized-at-rest
    store swaps in a ``quant_matmul`` head that tests pin against this
    definition), so train-time and serve-time personalization share one
    forward."""
    feat = clip_lib.encode_tokens(frozen, ccfg, x,
                                  lora=trainable.get("lora")) \
        if use_lora else x
    return client_lib.head_logits(frozen, trainable, feat, class_emb)


def slice_client_delta(stacked_delta, i: int):
    """Extract client ``i``'s delta from a stacked (possibly quantized)
    delta tree. QTensor leaves are re-wrapped with per-client metadata so
    slices taken from waves of different widths share one treedef (the
    async scheduler stacks buffered slices across waves) and
    ``tree_bytes`` reports the true per-client uplink payload."""
    def f(l):
        if isinstance(l, quant.QTensor):
            return quant.QTensor(
                q=l.q[i], scales=l.scales[i], bits=l.bits, mode=l.mode,
                block=l.block, out_dtype=l.out_dtype,
                orig_shape=tuple(l.orig_shape[1:]))
        return l[i]
    return jax.tree.map(f, stacked_delta,
                        is_leaf=lambda l: isinstance(l, quant.QTensor))


def comm_quantize_stacked(delta, strategy: Strategy):
    """Uplink-quantize a stacked delta tree (leading cohort axis) with
    semantics identical to each client quantizing its own delta:
    eligibility and block choice use the *per-client* leaf shape, and the
    blockwise absmax runs along trailing dims only, so the leading axis
    is inert."""
    if not strategy.comm_bits:
        return delta
    flat, treedef = jax.tree_util.tree_flatten_with_path(delta)
    out = []
    for path, leaf in flat:
        pstr = "/".join(str(k) for k in path)
        per_client = leaf.shape[1:]
        if not quant._quantizable(pstr, per_client, leaf.dtype,
                                  strategies_lib.COMM_MIN_SIZE,
                                  strategies_lib.COMM_SKIP):
            out.append(leaf)
            continue
        b = quant._pick_block(per_client[-2], strategies_lib.COMM_BLOCK)
        bits, mode = strategy.comm_bits, "linear"
        if b % 2:
            bits, mode = 8, "linear"
        out.append(quant.quantize(leaf, bits=bits, block=b, mode=mode))
    return jax.tree_util.tree_unflatten(treedef, out)


class CohortEngine:
    """One-dispatch-per-round federated executor.

    Built once per simulation from the instantiated clients; ``run_round``
    then advances the global trainables with a single jitted call
    returning per-client last-step loss/acc.
    """

    def __init__(self, *, frozen, ccfg, class_emb,
                 clients: Sequence[client_lib.Client], cfg: CohortConfig,
                 runtime=None, gan_job=None):
        self.cfg = cfg
        self.runtime = runtime if runtime is not None else \
            runtime_lib.ProgramRuntime()
        self.n_clients = len(clients)
        empty = [c.cid for c in clients if len(c.pool()[1]) == 0]
        if empty:
            raise ValueError(
                f"clients {empty} have empty pools; federated rounds "
                "(sequential or cohort) need every participant to hold "
                "data — drop them from the cohort")
        if gan_job is not None and cfg.mesh is not None:
            # the pending-GAN overlap path scatters into the staged
            # buffer with a plain .at[] update; keep the sharded layout
            # on the simple resolve-first path
            gan_job.resolve()
            gan_job = None
        if gan_job is not None:
            # Overlap fleet-GAN prep with pool staging: the GAN job's
            # rebalancing-set *sizes and labels* are host-known at launch
            # (rebalance_labels is a label histogram), so the padded pool
            # layout, lens, and labels are final now — only the
            # synthesized image contents are still computing on device.
            # Stage the raw rows immediately (the zero rows reserved for
            # the synthetic images are overwritten in feature space once
            # the job resolves below).
            pools = []
            for i, c in enumerate(clients):
                nd = gan_job.need.get(i, np.zeros((0,), np.int32))
                pools.append((
                    np.concatenate([
                        np.asarray(c.images, np.float32),
                        np.zeros((len(nd), *c.images.shape[1:]),
                                 np.float32)]),
                    np.concatenate([np.asarray(c.labels, np.int32),
                                    nd])))
        else:
            pools = [c.pool() for c in clients]
        imgs, labs, lens = stage_client_pools(pools)
        self.client_n = np.asarray([c.n for c in clients], np.float32)
        weights = self.client_n / self.client_n.sum()
        # trace-assigned compute heterogeneity: client i runs
        # local_steps * step_mult[i] steps; the fused program scans the
        # static max and masks the tail per client.
        self.step_mult = np.asarray(
            [c.local_steps_for(1) for c in clients], np.int32)
        if self.step_mult.max() > strategies_lib.MAX_STEP_MULT:
            raise ValueError(
                f"client step multipliers {self.step_mult.max()} exceed "
                f"strategies.MAX_STEP_MULT={strategies_lib.MAX_STEP_MULT}"
                " — the fused scan length must stay bounded")
        self.max_steps = cfg.local_steps * int(self.step_mult.max())
        self._het = bool(self.step_mult.max() > 1 or cfg.force_het)

        if cfg.mesh is not None:
            shards = mesh_lib.cohort_axis_size(cfg.mesh)
            if self.n_clients % shards:
                raise ValueError(
                    f"cohort of {self.n_clients} clients not divisible by "
                    f"the mesh's {shards} data-parallel shards")
            put = lambda x: jax.device_put(
                x, mesh_lib.cohort_sharding(cfg.mesh, np.ndim(x)))
        else:
            shards = 1
            put = jnp.asarray
        # cohort-axis shard count: subset/wave widths bucket to shard
        # multiples (runtime.bucket_width(shards=...)) and the
        # in-program FedAvg runs hierarchically (shard-local partial
        # sums -> global reduce) so the full stacked delta is never
        # reduced on one device
        self.shards = shards
        self._put = put
        self._rep = mesh_lib.replicated_sharding(cfg.mesh) \
            if cfg.mesh is not None else None

        # Hoist every trainable-independent prefix of the forward out of
        # the training loop — staging the pool once per engine makes this
        # a one-time cost instead of a per-step one:
        #  - no LoRA: the whole frozen backbone; the pool is stored as
        #    pooled features (C, P, d) and local steps train only the
        #    adapter head;
        #  - with LoRA: the patch embedding (+cls+pos), which LoRA never
        #    touches; the pool is stored as embedded tokens
        #    (C, P, S, d).
        # GAN-rebalanced pools (fl.fleetgan) arrive here already
        # augmented via Client.pool() and stage like any other pool.
        self.pool_staged = stage_encoded_pools(
            frozen, ccfg, use_lora=cfg.strategy.use_lora, imgs=imgs,
            put=put, runtime=self.runtime)
        self.pool_labs = put(labs)
        # lens stays replicated: it feeds the dedicated host-side batch
        # index draw (sample_batch_indices), never the sharded round
        self.lens = jnp.asarray(lens, jnp.int32)
        self.weights = put(weights.astype(np.float32))
        self.frozen = frozen
        self.class_emb = class_emb
        self.ccfg = ccfg
        self._uplink_per_client: Optional[int] = None
        # programs the engine closes over self.cfg/self.ccfg for: the
        # runtime cache key must carry those statics so engines sharing
        # one runtime (benchmark sweeps) never collide. The LoRA matmul
        # routing (fused op vs legacy einsum chain, REPRO_LORA_FUSED) is
        # read at trace time inside core.lora.linear, so it is a static
        # of the traced program too — without it a bench flipping the
        # env var between engines would hit a stale executable compiled
        # for the other path
        self._static_key = (cfg.strategy, ccfg, cfg.local_steps,
                            cfg.batch_size, cfg.lr, self._het,
                            self.max_steps, cfg.mesh,
                            lora_lib._fused_enabled())
        if gan_job is not None:
            self._merge_gan_features(gan_job, clients)

    def _merge_gan_features(self, gan_job, clients):
        """Land a pending fleet-GAN job into the already-staged pools:
        resolve the job (blocks on the GAN device work that overlapped
        staging), encode the synthesized rows through the same staging
        program, and scatter them into their reserved slots. One staging
        pipeline, two passes over disjoint rows."""
        gan_job.resolve()
        # chaos: clients that dropped between launch and resolve never
        # delivered their synthesized rows, but the padded pool layout
        # (fixed at launch) reserved slots for them — shrink their lens
        # back to the raw pool so the zero-feature reserved rows are
        # never sampled (lens is the sampling bound, so this is exact)
        dropped = [i for i in sorted(getattr(gan_job, "dropped", ()))
                   if len(gan_job.need.get(i, ())) > 0]
        if dropped:
            raw = jnp.asarray([clients[i].n for i in dropped], jnp.int32)
            self.lens = self.lens.at[jnp.asarray(dropped)].set(raw)
        aug = [(i, c.aug_images) for i, c in enumerate(clients)
               if c.aug_images is not None and len(c.aug_images)]
        if not aug:
            return
        rows = np.concatenate([a for _, a in aug]).astype(np.float32)
        feats = encode_rows(
            self.frozen, self.ccfg, use_lora=self.cfg.strategy.use_lora,
            rows=rows, runtime=self.runtime)
        ci = np.concatenate([np.full(len(a), i, np.int32)
                             for i, a in aug])
        # synthetic rows sit right after client i's raw rows (the pool
        # layout Client.pool() produces)
        ri = np.concatenate([clients[i].n + np.arange(len(a))
                             for i, a in aug]).astype(np.int32)
        self.pool_staged = self.pool_staged.at[
            jnp.asarray(ci), jnp.asarray(ri)].set(feats)

    def _sample_idx(self, key, lens, steps: int):
        """Per-round batch indices through the runtime cache (kind
        ``sample_idx`` — one tiny program per distinct selection
        width)."""
        batch = self.cfg.batch_size

        def build():
            return lambda k, l: sample_batch_indices(k, l, steps, batch)

        args = (key, lens)
        return self.runtime.run(
            "sample_idx", build, args,
            static_key=(steps, batch))

    # -- uplink accounting --------------------------------------------
    def per_client_uplink_bytes(self, global_tr) -> int:
        """One client's (quantized) delta payload. Shape-only (no device
        work), computed once via the spec path of the quantizer; exact
        for every participant because quantization is leading-axis-inert
        and all clients share the trainable shapes."""
        if self._uplink_per_client is None:
            specs = jax.tree.map(
                lambda g: jax.ShapeDtypeStruct(g.shape, jnp.float32),
                global_tr)
            if self.cfg.strategy.comm_bits:
                specs = quant.quantize_tree_specs(
                    specs, bits=self.cfg.strategy.comm_bits,
                    block=strategies_lib.COMM_BLOCK,
                    min_size=strategies_lib.COMM_MIN_SIZE,
                    skip_names=strategies_lib.COMM_SKIP)
            self._uplink_per_client = tree_bytes(specs)
        return self._uplink_per_client

    def uplink_bytes(self, global_tr) -> int:
        """Full-cohort round uplink: n_clients x per-client delta size."""
        return self.n_clients * self.per_client_uplink_bytes(global_tr)

    # -- the fused round ----------------------------------------------
    def _local_train(self, frozen, class_emb, tr, staged, labs, ix,
                     n_steps=None):
        """One client's local training (vmapped over the cohort axis),
        shared by the full, subset, and wave programs. ``n_steps`` (a
        traced scalar) masks the tail of the fixed-length scan for
        heterogeneous step counts; ``None`` keeps the unmasked PR 1
        program byte-for-byte."""
        lr = self.cfg.lr
        ccfg = self.ccfg
        use_lora = self.cfg.strategy.use_lora
        opt = optim.adam_init(tr)

        def grad_fn(t, ixt):
            with jax.named_scope("forward"):
                bx, by = staged[ixt], labs[ixt]

            def loss_fn(tt):
                with jax.named_scope("forward"):
                    logits = client_logits(frozen, ccfg, tt, bx, class_emb,
                                           use_lora=use_lora)
                    return (losses.cross_entropy(logits, by),
                            losses.accuracy(logits, by))

            (loss, acc), g = jax.value_and_grad(
                loss_fn, has_aux=True)(t)
            return g, (loss, acc)

        active = None if n_steps is None else \
            optim.step_mask(n_steps, ix.shape[0])
        tr, opt, (ls, accs) = optim.adam_scan(
            grad_fn, tr, opt, ix, lr=lr, grad_clip=1.0, active=active)
        if n_steps is None:
            return tr, ls[-1], accs[-1]
        return tr, jnp.take(ls, n_steps - 1), jnp.take(accs, n_steps - 1)

    def _train_cohort(self, global_tr, staged, labs, idx, n_steps,
                      frozen, class_emb):
        """Broadcast the global trainables over the cohort, train every
        client, and return (stacked quantized deltas, loss, acc). On a
        mesh the training runs under ``shard_map``: each device trains
        its own rows of the cohort axis. Clients are independent, so this
        is exact, and Pallas kernels need it — a Mosaic call cannot be
        partitioned automatically."""
        het = (n_steps,) if n_steps is not None else ()

        def train(global_tr, frozen, class_emb, staged, labs, idx, *het):
            C = idx.shape[0]
            cohort_tr = jax.tree.map(
                lambda g: jnp.broadcast_to(g[None], (C,) + g.shape),
                global_tr)
            return jax.vmap(
                lambda tr, s, l, ix, *n: self._local_train(
                    frozen, class_emb, tr, s, l, ix, *n))(
                cohort_tr, staged, labs, idx, *het)

        if self.shards > 1:
            rows = P(mesh_lib.dp_axes(self.cfg.mesh))
            train = jax.shard_map(
                train, mesh=self.cfg.mesh,
                in_specs=(P(),) * 3 + (rows,) * (3 + len(het)),
                out_specs=rows, check_vma=False)
        after, loss, acc = train(global_tr, frozen, class_emb, staged,
                                 labs, idx, *het)
        with jax.named_scope("uplink"):
            delta = jax.tree.map(
                lambda a, g: (a - g[None]).astype(jnp.float32),
                after, global_tr)
            delta = comm_quantize_stacked(delta, self.cfg.strategy)
        return delta, loss, acc

    def _aggregate(self, global_tr, weights, delta):
        """In-program FedAvg. Unsharded engines keep the flat
        ``aggregate_stacked`` reduction bit-for-bit (the K=N == full
        round identity depends on it); mesh engines aggregate
        hierarchically — each shard reduces its own cohort rows to a
        partial sum + partial mass and only the (shards, ...) partials
        cross the mesh — so the stacked delta is never reduced on one
        device. Tree == flat within fp tolerance (re-association),
        pinned by the hypothesis property in tests/test_runtime.py."""
        with jax.named_scope("fedavg"):
            if self.shards > 1:
                return server.aggregate_tree(global_tr, weights, delta,
                                             n_shards=self.shards)
            return server.aggregate_stacked(global_tr, weights, delta)

    def _build_round(self):
        def round_fn(global_tr, idx, pool_staged, pool_labs, weights,
                     frozen, class_emb):
            delta, loss, acc = self._train_cohort(
                global_tr, pool_staged, pool_labs, idx, None, frozen,
                class_emb)
            new_global = self._aggregate(global_tr, weights, delta)
            return new_global, loss, acc

        return round_fn

    def _build_subset_round(self):
        """Sync-partial round at a fixed (bucketed) cohort width: gather
        the selected clients' already-staged pools (no re-upload, one
        compile per width bucket), train, quantize, and aggregate
        in-program with the host-normalized subset weights (zero for pad
        rows)."""
        het = self._het

        def round_fn(global_tr, sel, n_steps, idx, pool_staged,
                     pool_labs, weights, frozen, class_emb):
            staged = jnp.take(pool_staged, sel, axis=0)
            labs = jnp.take(pool_labs, sel, axis=0)
            delta, loss, acc = self._train_cohort(
                global_tr, staged, labs, idx, n_steps if het else None,
                frozen, class_emb)
            new_global = self._aggregate(global_tr, weights, delta)
            return new_global, loss, acc

        return round_fn

    def _build_wave(self):
        """Async wave: identical local training, but the program stops
        before aggregation and returns the stacked quantized deltas — the
        scheduler buffers them on the host and commits with
        staleness-discounted weights later. No donation: the caller's
        global trainables stay alive for the commit."""
        het = self._het

        def wave_fn(global_tr, sel, n_steps, idx, pool_staged,
                    pool_labs, frozen, class_emb):
            staged = jnp.take(pool_staged, sel, axis=0)
            labs = jnp.take(pool_labs, sel, axis=0)
            return self._train_cohort(
                global_tr, staged, labs, idx, n_steps if het else None,
                frozen, class_emb)

        return wave_fn

    def _canon_global(self, global_tr):
        """Pin the global trainables to the canonical mesh-replicated
        placement before a sharded dispatch. A sharded round's OUTPUT
        trainables come back replicated over the mesh, so without this
        the warmup round (host-resident inputs) and every chained round
        (replicated inputs) would compile separate executables under
        the sharding-aware runtime cache keys; device_put is a no-op
        once the placement already matches."""
        if self._rep is None:
            return global_tr
        return jax.tree.map(lambda g: jax.device_put(g, self._rep),
                            global_tr)

    def _donate(self):
        return (0,) if self.cfg.donate else ()

    def _bucket_inputs(self, sel_dev, n_steps, idx, B: int):
        """Pad the cohort-axis inputs of a width-K selection to the
        width-B bucket: pad rows gather client 0's staged pool, sample
        index 0 every step, and run the minimum step count — all of it
        thrown away (zero aggregation weight, metrics sliced to K).
        The true rows' arrays are untouched: indices were drawn at the
        true K *before* padding, so the sample stream is exactly the
        unbucketed one."""
        return (runtime_lib.pad_leading(sel_dev, B),
                runtime_lib.pad_leading(n_steps, B, fill=1),
                runtime_lib.pad_leading(idx, B))

    def _subset_inputs(self, sel, key, n_steps=None):
        sel = np.asarray(sel, np.int32)
        order = np.argsort(sel, kind="stable")
        sel = sel[order]
        if len(np.unique(sel)) != len(sel) or sel.min() < 0 or \
                sel.max() >= self.n_clients:
            raise ValueError(f"invalid client subset {sel}")
        if n_steps is None:
            n_steps = self.cfg.local_steps * self.step_mult[sel]
        else:
            # caller-supplied (scheduler trace) step counts, reordered
            # with the selection sort — they are the single source of
            # truth, so a profile the staged program cannot honor fails
            # loudly instead of silently training different counts
            n_steps = np.asarray(n_steps, np.int32)[order]
            if n_steps.shape != sel.shape:
                raise ValueError(
                    f"n_steps shape {n_steps.shape} != sel {sel.shape}")
            if n_steps.min() < 1 or n_steps.max() > self.max_steps:
                raise ValueError(
                    f"n_steps {n_steps} outside [1, {self.max_steps}] "
                    "(engine staged with max step multiplier "
                    f"{int(self.step_mult.max())})")
            if not self._het and np.any(n_steps != self.cfg.local_steps):
                raise ValueError(
                    "engine was staged homogeneous (every client "
                    "step_mult==1) but the scheduler requested "
                    f"heterogeneous step counts {n_steps}; set "
                    "Client.step_mult before building the engine")
        sel_dev = jnp.asarray(sel)
        lens_sel = jnp.take(self.lens, sel_dev)
        # indices are drawn at the TRUE selection width, before any
        # bucket padding — threefry draws are not shape-stable, so the
        # pad must never touch the sample stream (round_indices stays
        # the oracle for the real rows)
        idx = self._sample_idx(key, lens_sel, self.max_steps)
        return sel, sel_dev, jnp.asarray(n_steps, jnp.int32), idx

    def run_subset_round(self, global_tr, sel, key, n_steps=None):
        """Sync-partial round over client positions ``sel`` (treated as a
        set; canonicalized to sorted order so selection is
        permutation-invariant and K=N reproduces the full round).
        Aggregation weights are the selected clients' sample counts,
        renormalized over the subset — padding rows of the width bucket
        carry weight zero. ``n_steps`` optionally overrides the
        per-client step counts (aligned with ``sel``'s order)."""
        sel, sel_dev, n_steps, idx = self._subset_inputs(sel, key,
                                                         n_steps)
        K = len(sel)
        B = runtime_lib.bucket_width(K, self.n_clients,
                                     shards=self.shards)
        weights = np.zeros(B, np.float32)
        weights[:K] = self.client_n[sel] / self.client_n[sel].sum()
        server.check_weights(weights, B)
        if B > K:
            sel_dev, n_steps, idx = self._bucket_inputs(
                sel_dev, n_steps, idx, B)
        weights = self._put(weights)
        if self.cfg.mesh is not None:
            sel_dev, n_steps, idx = (self._put(sel_dev),
                                     self._put(n_steps), self._put(idx))
            global_tr = self._canon_global(global_tr)
        uplink = K * self.per_client_uplink_bytes(global_tr)
        args = (global_tr, sel_dev, n_steps, idx, self.pool_staged,
                self.pool_labs, weights, self.frozen, self.class_emb)
        new_tr, loss, acc = self.runtime.run(
            "subset_round", self._build_subset_round, args,
            static_key=self._static_key,
            donate_argnums=self._donate())
        # metrics stay device-resident (sliced to the true K in-graph):
        # the caller decides when to materialize — the pipelined round
        # loop defers them to its bulk ring flush
        return new_tr, {
            "loss": loss[:K], "acc": acc[:K],
            "uplink_bytes": uplink,
            "sel": sel}

    def run_wave(self, global_tr, sel, key, n_steps=None):
        """Train client positions ``sel`` from ``global_tr`` without
        committing: returns (stacked quantized delta tree, metrics).
        Slice per-client updates out with ``slice_client_delta`` — the
        true clients occupy rows [0, K) of the width bucket; pad rows
        are never sliced or committed."""
        sel, sel_dev, n_steps, idx = self._subset_inputs(sel, key,
                                                         n_steps)
        K = len(sel)
        B = runtime_lib.bucket_width(K, self.n_clients,
                                     shards=self.shards)
        if B > K:
            sel_dev, n_steps, idx = self._bucket_inputs(
                sel_dev, n_steps, idx, B)
        if self.cfg.mesh is not None:
            sel_dev, n_steps, idx = (self._put(sel_dev),
                                     self._put(n_steps), self._put(idx))
            global_tr = self._canon_global(global_tr)
        args = (global_tr, sel_dev, n_steps, idx, self.pool_staged,
                self.pool_labs, self.frozen, self.class_emb)
        delta, loss, acc = self.runtime.run(
            "wave_round", self._build_wave, args,
            static_key=self._static_key)
        return delta, {
            "loss": loss[:K], "acc": acc[:K],
            "uplink_bytes": K * self.per_client_uplink_bytes(global_tr),
            "sel": sel}

    def run_round(self, global_tr, key):
        """Advance one full-cohort federated round. Returns
        (new_global_trainables, metrics) where metrics carries per-client
        last-step loss/acc and the round's uplink byte count."""
        if self._het:
            raise ValueError(
                "run_round is the homogeneous (unmasked) full-cohort "
                f"program, but clients carry step_mult {self.step_mult}"
                " — use run_subset_round(sel=arange(n_clients)) so the "
                "masked scan honors the heterogeneous step counts")
        uplink = self.uplink_bytes(global_tr)
        idx = self._sample_idx(key, self.lens, self.cfg.local_steps)
        if self.cfg.mesh is not None:
            idx = self._put(idx)
            global_tr = self._canon_global(global_tr)
        args = (global_tr, idx, self.pool_staged, self.pool_labs,
                self.weights, self.frozen, self.class_emb)
        new_tr, loss, acc = self.runtime.run(
            "full_round", self._build_round, args,
            static_key=self._static_key,
            donate_argnums=self._donate())
        return new_tr, {"loss": loss, "acc": acc,
                        "uplink_bytes": uplink}
