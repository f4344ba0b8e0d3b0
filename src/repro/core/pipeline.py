"""Hydra's shard-parallel execution engine.

The paper's core idea — run *shards of K independent models* concurrently so a
device idled by one model's sequential dependency works on another model — is
compiled here into a single SPMD program:

  * the ``model`` mesh axis holds pipeline *stages* (= the paper's shards);
  * the slot stream interleaves (trial k, microbatch m) pairs round-robin;
  * one ``lax.scan`` over ticks advances every stage one slot per tick, with
    activations hopping stage→stage via ``lax.ppermute`` over the ICI ring;
  * embedding and LM head are **vocab-parallel over the stage axis** (tokens
    are replicated across stages, so a masked-local-gather + psum is exact and
    the head matmul is split S ways instead of idling S−1 stages);
  * gradients come from ``jax.grad`` *through* the scanned pipeline — AD
    reverses the ppermute schedule automatically, so each trial's gradient is
    exactly the unpipelined gradient (paper desideratum D3).

Per-trial optimizer updates (vmapped hyperparameters over the K axis) and the
data/pod-axis gradient reductions also live inside the shard_map so every
collective is explicit and visible to the roofline analyzer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.partitioner import StagePlan, plan_stages
from repro.models import blocks as BLK
from repro.models import lm
from repro.models.layers import ModelOptions


# ---------------------------------------------------------------------------
# Engine configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static configuration of one Hydra gang (same-architecture trials).

    In serving, the K trial rows double as the *co-serving* axis: each row
    holds one model variant's weights and caches, and the serve engine routes
    per-arch request streams into the matching rows (see repro/serve/).
    """

    n_trials: int  # K — concurrent models (the paper's task-parallel level)
    n_microbatches: int  # M — slots per trial per step
    microbatch: int  # per-(data×pod)-replica microbatch size
    n_stages: int  # size of the stage ("model") mesh axis
    data_size: int = 1  # size of the data axis
    pod_size: int = 1  # size of the pod axis (1 = single pod)
    stage_axis: str = "model"
    data_axis: str = "data"
    pod_axis: Optional[str] = None
    fsdp: bool = False  # ZeRO-style: shard layer weights over data axis
    vocab_parallel: bool = True
    batch_replicated: bool = False  # batch too small to shard (long_500k)
    window: int = 0  # sliding window for attention (long-context serving)
    max_seq: int = 0  # cache length for serving
    cache_dtype: Any = jnp.bfloat16
    # --- paged KV-cache (serving only; see repro/serve/paging.py) ----------
    paged: bool = False  # serve KV in a shared block pool instead of dense
    # per-slot max_seq strips (attention families only)
    block_size: int = 16  # tokens per block
    n_blocks: int = 0  # pool size PER TRIAL (the paged cache leaf carries a
    # leading K axis — each co-served variant owns its own pool); rows sharded
    # over the data/pod axes each own an equal pool slice (n_blocks /
    # dp_degree blocks per shard per trial)
    host_blocks: int = 0  # host-memory spill tier PER POOL PARTITION (serve
    # BlockStore): evicted prefix-cache blocks and retracted requests' KV
    # swap out here instead of being destroyed; 0 = no host tier
    # --- §Perf knobs (baseline: all off/default) ---------------------------
    skip_bubbles: bool = False  # cond-skip fill/drain ticks (compute+gathers;
    # safe: validity is uniform over every axis the inner collectives span)
    prefill_chunks: int = 1  # >1: chunked prefill — sequence chunks become
    # extra pipeline slots (Hydra's slot-filling applied within one request);
    # chunk c attends to the cache written by chunks < c (mode="append")
    layer_remat: bool = True  # inner per-layer checkpoint inside the
    # tick-level one; the tick also keeps the inputs of as many layers as
    # fit (``train_stash``; on one stage all, as one tick is live at a
    # time), which its backward then does not replay twice.
    # False = tick-level remat only: one fewer weight-gather round in backward

    @property
    def n_slots(self) -> int:
        return self.n_trials * self.n_microbatches

    @property
    def n_ticks(self) -> int:
        return self.n_slots + self.n_stages - 1

    @property
    def stash_ticks(self) -> int:
        """Ticks whose activations the train step's backward holds at once:
        one for a one-stage gang, whose step runs each tick's backward
        right after its forward (``tickwise_train_grads``); every tick for
        a pipeline, whose backward follows all its ticks' forwards."""
        return 1 if self.n_stages == 1 else self.n_ticks

    @property
    def dp_axes(self):
        """Axes carrying data parallelism (batch sharding + grad reduction)."""
        if self.pod_axis is not None:
            return (self.pod_axis, self.data_axis)
        return (self.data_axis,)

    @property
    def bubble_fraction(self) -> float:
        return (self.n_stages - 1) / self.n_ticks

    @property
    def cache_groups(self) -> int:
        """Distinct caches in serving: chunked prefill shares one cache per
        request group across its sequence-chunk slots."""
        if self.prefill_chunks > 1:
            return self.n_microbatches // self.prefill_chunks
        return self.n_microbatches

    def padded_vocab(self, vocab: int) -> int:
        s = self.n_stages
        return -(-vocab // s) * s


# ---------------------------------------------------------------------------
# Parameter layout: trial-stacked, stage-sharded (+ optional FSDP)
# ---------------------------------------------------------------------------


def _fsdp_dim(path_leaf_shape, data_size: int) -> Optional[int]:
    """Pick the dim (of the unstacked layer leaf) to shard over the data axis.

    Prefer the first matrix dim divisible by the data-axis size; vectors stay
    replicated.
    """
    if len(path_leaf_shape) < 2:
        return None
    for d, size in enumerate(path_leaf_shape):
        if size % data_size == 0 and size >= data_size:
            return d
    return None


def trial_params_struct(cfg: ArchConfig, eng: EngineConfig, plan: StagePlan,
                        dtype=jnp.bfloat16, max_pos: int = 0):
    """ShapeDtypeStructs of the trial-stacked parameter pytree (dry-run)."""
    one = jax.eval_shape(
        lambda k: lm.init_params(cfg, k, dtype=dtype, max_pos=max_pos,
                                 n_layers=plan.padded_layers),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    vpad = eng.padded_vocab(cfg.vocab_size)

    def fix(path, s):
        shape = (eng.n_trials,) + s.shape
        name = "/".join(str(p.key) if hasattr(p, "key") else str(p)
                        for p in path)
        if name == "embed/tok":
            shape = (eng.n_trials, vpad, cfg.d_model)
        if name == "head":
            shape = (eng.n_trials, cfg.d_model, vpad)
        return jax.ShapeDtypeStruct(shape, s.dtype)

    return jax.tree_util.tree_map_with_path(fix, one)


@functools.lru_cache(maxsize=32)
def _trial_params_init(cfg: ArchConfig, eng: EngineConfig, plan: StagePlan,
                       dtype, max_pos: int, mesh) -> Callable:
    def init_params(key):
        keys = jax.random.split(key, eng.n_trials)
        params = jax.vmap(
            lambda k: lm.init_params(cfg, k, dtype=dtype, max_pos=max_pos,
                                     n_layers=plan.padded_layers))(keys)
        vpad = eng.padded_vocab(cfg.vocab_size)
        if vpad != cfg.vocab_size:
            pad = vpad - cfg.vocab_size
            params["embed"]["tok"] = jnp.pad(
                params["embed"]["tok"], ((0, 0), (0, pad), (0, 0)))
            if "head" in params:
                params["head"] = jnp.pad(params["head"],
                                         ((0, 0), (0, 0), (0, pad)))
        return params

    shardings = None
    if mesh is not None:
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 param_pspecs(cfg, eng),
                                 is_leaf=lambda x: isinstance(x, P))
    return jax.jit(init_params, out_shardings=shardings)


def init_trial_params(cfg: ArchConfig, eng: EngineConfig, plan: StagePlan,
                      key, dtype=jnp.float32, max_pos: int = 0, mesh=None):
    """Materialize K trials' parameters (stacked on a leading K axis).

    One jitted program: XLA fuses each leaf's RNG, scale and cast, so every
    leaf is written once in ``dtype`` — no fp32 copy and no per-op
    temporaries beside it (a bf16 chatglm3-6b fills most of one 16 GB chip,
    so an eager init would not fit). ``mesh`` writes each leaf straight
    into its ``param_pspecs`` sharding instead of onto the default device.
    """
    return _trial_params_init(cfg, eng, plan, jnp.dtype(dtype), max_pos,
                              mesh)(key)


def param_pspecs(cfg: ArchConfig, eng: EngineConfig):
    """PartitionSpec pytree for the trial-stacked params.

    layers/*   : (K, Lp, ...)   -> P(None, stage, [fsdp-dim over data])
    embed/tok  : (K, Vp, D)     -> P(None, stage, None)  [vocab-parallel]
    embed/pos  : (K, maxpos, D) -> P(None, stage, None)  [position-parallel]
    head       : (K, D, Vp)     -> P(None, None, stage)
    final_norm : replicated ; shared/* : replicated (grads psum'd over stage)
    """
    st, da = eng.stage_axis, eng.data_axis
    plan = plan_stages(cfg, eng.n_stages)
    struct = trial_params_struct(cfg, eng, plan)

    def spec(path, leaf):
        name = "/".join(str(p.key) if hasattr(p, "key") else str(p)
                        for p in path)
        if name.startswith("layers/"):
            rest = [None] * (leaf.ndim - 2)
            if eng.fsdp:
                d = _fsdp_dim(leaf.shape[2:], eng.data_size)
                if d is not None:
                    rest[d] = da
            return P(None, st, *rest)
        if name == "embed/tok" or name == "embed/pos":
            if eng.vocab_parallel:
                return P(None, st, *([None] * (leaf.ndim - 2)))
            return P(*([None] * leaf.ndim))
        if name == "head":
            if eng.vocab_parallel:
                return P(None, None, st)
            return P(*([None] * leaf.ndim))
        return P(*([None] * leaf.ndim))  # final_norm, shared/*

    return jax.tree_util.tree_map_with_path(spec, struct)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding / loss / sampling (stage-axis collectives)
# ---------------------------------------------------------------------------


def _stage_info(eng: EngineConfig):
    s_idx = lax.axis_index(eng.stage_axis)
    return s_idx, eng.n_stages


def vp_embed(cfg: ArchConfig, eng: EngineConfig, embed_local, tokens,
             positions=None, compute_dtype=jnp.float32):
    """Vocab-parallel embedding: masked local gather + psum over stages.

    Tokens are replicated across the stage axis, so each stage gathers the
    rows it owns and the psum reconstitutes the full embedding exactly.
    """
    s_idx, n_stages = _stage_info(eng)
    tok_tab = embed_local["tok"]  # (V_pad/S, D)
    v_s = tok_tab.shape[0]
    local = tokens - s_idx * v_s
    valid = (local >= 0) & (local < v_s)
    rows = jnp.take(tok_tab, jnp.clip(local, 0, v_s - 1), axis=0)
    part = jnp.where(valid[..., None], rows, 0).astype(compute_dtype)
    if cfg.rope == "learned" and positions is not None and "pos" in embed_local:
        pos_tab = embed_local["pos"]  # (maxpos/S, D)
        p_s = pos_tab.shape[0]
        plocal = positions - s_idx * p_s
        pvalid = (plocal >= 0) & (plocal < p_s)
        prows = jnp.take(pos_tab, jnp.clip(plocal, 0, p_s - 1), axis=0)
        part = part + jnp.where(pvalid[..., None], prows, 0).astype(compute_dtype)
    return lax.psum(part, eng.stage_axis)


def plain_embed(cfg, eng, embed_local, tokens, positions=None,
                compute_dtype=jnp.float32):
    x = jnp.take(embed_local["tok"], tokens, axis=0).astype(compute_dtype)
    if cfg.rope == "learned" and positions is not None and "pos" in embed_local:
        tab = embed_local["pos"]
        x = x + jnp.take(tab, jnp.minimum(positions, tab.shape[0] - 1),
                         axis=0).astype(compute_dtype)
    return x


def vp_loss(cfg: ArchConfig, eng: EngineConfig, norm_p, head_local, y,
            labels):
    """Vocab-parallel cross-entropy (mean over tokens). y (b,s,D) replicated
    across stages; head_local (D, V_pad/S)."""
    s_idx, n_stages = _stage_info(eng)
    x = lm.final_norm_apply(cfg, norm_p, y)
    logits = jnp.einsum("bsd,dv->bsv", x, head_local).astype(jnp.float32)
    v_s = logits.shape[-1]
    gid = s_idx * v_s + jnp.arange(v_s)
    logits = jnp.where(gid < cfg.vocab_size, logits, -1e30)
    # the shift is a pure stabilizer — logsumexp is shift-invariant, so
    # stop_gradient is exact (pmax has no AD rule; gather+max does)
    lmax = jnp.max(
        lax.all_gather(lax.stop_gradient(jnp.max(logits, axis=-1)),
                       eng.stage_axis, axis=0), axis=0)
    sumexp = lax.psum(jnp.sum(jnp.exp(logits - lmax[..., None]), axis=-1),
                      eng.stage_axis)
    local_label = labels - s_idx * v_s
    owned = (local_label >= 0) & (local_label < v_s)
    ll = jnp.take_along_axis(
        logits, jnp.clip(local_label, 0, v_s - 1)[..., None], axis=-1)[..., 0]
    ll = lax.psum(jnp.where(owned, ll, 0.0), eng.stage_axis)
    nll = jnp.log(sumexp) + lmax - ll
    return nll.mean()


def vp_greedy_tokens(cfg: ArchConfig, eng: EngineConfig, norm_p, head_local,
                     y):
    """Vocab-parallel greedy argmax at EVERY position. y (b, s, D) ->
    ((b, s) int32 winners, (b, s) float32 max logits). The per-position math
    is identical to :func:`vp_greedy_token` — speculative verify relies on
    position i of an s-wide call matching a 1-wide call at that depth."""
    s_idx, _ = _stage_info(eng)
    x = lm.final_norm_apply(cfg, norm_p, y)
    logits = jnp.einsum("bsd,dv->bsv", x, head_local).astype(jnp.float32)
    v_s = logits.shape[-1]
    gid = s_idx * v_s + jnp.arange(v_s)
    logits = jnp.where(gid < cfg.vocab_size, logits, -1e30)
    lmax = jnp.max(logits, axis=-1)  # (b, s)
    larg = jnp.argmax(logits, axis=-1) + s_idx * v_s
    gmax = lax.pmax(lmax, eng.stage_axis)
    winner = lax.psum(jnp.where(lmax >= gmax, larg, 0), eng.stage_axis)
    count = lax.psum((lmax >= gmax).astype(jnp.int32), eng.stage_axis)
    return winner // jnp.maximum(count, 1), gmax  # (b, s), (b, s)


def vp_greedy_token(cfg: ArchConfig, eng: EngineConfig, norm_p, head_local,
                    y):
    """Vocab-parallel greedy sampling of the next token. y (b, 1, D)."""
    tok, gmax = vp_greedy_tokens(cfg, eng, norm_p, head_local, y)
    return tok[:, 0], gmax[:, 0]  # (b,), (b,)


def plain_loss(cfg, eng, norm_p, head_full, y, labels):
    x = lm.final_norm_apply(cfg, norm_p, y)
    logits = jnp.einsum("bsd,dv->bsv", x, head_full)
    return lm.cross_entropy(logits, labels)


# ---------------------------------------------------------------------------
# FSDP per-layer gather hook
# ---------------------------------------------------------------------------


def make_layer_gather(cfg: ArchConfig, eng: EngineConfig):
    """Returns fn applied to one layer's (local) params inside the stage scan:
    all-gathers the data-axis-sharded dims back to full size. Its AD transpose
    is a reduce-scatter, which IS the FSDP gradient reduction."""
    if not eng.fsdp:
        return None
    specs = param_pspecs(cfg, eng)["layers"]

    def gather(p_layer):
        def one(spec, leaf):
            # spec corresponds to (K, Lp, ...); leaf here is (...) per layer
            dims = list(spec)[2:]
            for d, ax in enumerate(dims):
                if ax == eng.data_axis:
                    out = lax.all_gather(leaf, eng.data_axis, axis=d,
                                         tiled=True)
                    # pin the gather to the param dtype: without the barrier
                    # XLA commutes downstream fp32 converts across the gather
                    # (2× ICI traffic and full-leaf fp32 temps — see the
                    # buffer-dump analysis in EXPERIMENTS.md §Perf)
                    return lax.optimization_barrier(out)
            return leaf

        return jax.tree.map(one, specs, p_layer,
                            is_leaf=lambda x: isinstance(x, P))

    return gather


# ---------------------------------------------------------------------------
# The pipelined forward (shared by train loss and serving)
# ---------------------------------------------------------------------------


def _slot_ids(eng: EngineConfig, slot):
    k = jnp.clip(slot % eng.n_trials, 0, eng.n_trials - 1)
    m = jnp.clip(slot // eng.n_trials, 0, eng.n_microbatches - 1)
    return k, m


def _take2(tree, i, j):
    """tree leaves (K, M, ...) -> (...) at [i, j] (dynamic)."""
    return jax.tree.map(
        lambda l: lax.dynamic_index_in_dim(
            lax.dynamic_index_in_dim(l, i, 0, keepdims=False),
            j, 0, keepdims=False), tree)


def _take1(tree, i):
    return jax.tree.map(
        lambda l: lax.dynamic_index_in_dim(l, i, 0, keepdims=False), tree)


# checkpoint name of a tick's stage output (kept with the layer inputs)
STAGE_OUTPUT = "stage_output"


def train_stash(cfg: ArchConfig, opts: ModelOptions, eng: EngineConfig,
                seq_len: int, param_dtype) -> tuple:
    """The activations the train step's backward keeps per chip, as
    (layers, bytes): the layer inputs each tick keeps
    (``scheduler.stash_layers``, from the shapes, the dtypes and the chip's
    HBM) and the stash's bytes (``scheduler.stash_bytes``). With
    ``layers`` > 0 each tick keeps the inputs of its stage's last
    ``layers`` layers and the stage output, so its backward replays only
    the other layers before each layer replays its own forward inside its
    checkpoint: a kept layer's forward runs twice in a step, not three
    times. With 0, the tick stash: each tick keeps its stage input only.
    Without ``opts.remat`` no tick is rematerialized: ``(0, 0)``.
    """
    if not opts.remat:
        return 0, 0
    # imported here: the scheduler imports this module
    from repro.core.scheduler import stash_bytes, stash_layers
    act = jnp.dtype(opts.compute_dtype).itemsize
    kept = stash_layers(cfg, eng, seq_len, jnp.dtype(param_dtype).itemsize,
                        act)
    return kept, stash_bytes(cfg, eng, seq_len, kept, act)


def pipeline_train_loss(cfg: ArchConfig, opts: ModelOptions, eng: EngineConfig,
                        params, batch, stash_layers: Optional[int] = None):
    """Runs the multi-trial pipelined forward; returns per-trial (loss, aux).

    Executes *inside* shard_map. ``params`` leaves are local shards:
    layers (K, L_s, ...), embed/tok (K, V_s, D), head (K, D, V_s), etc.
    batch: tokens/labels (K, M, mb, seq) + optional extras.
    ``stash_layers``: the layer inputs each tick keeps for its backward;
    by default ``train_stash``'s for this engine.
    """
    S = eng.n_stages
    K, M = eng.n_trials, eng.n_microbatches
    plan = plan_stages(cfg, S)
    l_s = plan.layers_per_stage
    s_idx = lax.axis_index(eng.stage_axis)
    layer_offset = s_idx * l_s
    layer_mask = (layer_offset + jnp.arange(l_s)) < cfg.n_layers
    gather_fn = make_layer_gather(cfg, eng)

    tokens, labels = batch["tokens"], batch["labels"]
    mb, seq = tokens.shape[-2], tokens.shape[-1]
    d = cfg.d_model
    cdt = opts.compute_dtype
    pos_train = jnp.broadcast_to(jnp.arange(seq), (mb, seq))
    kept = stash_layers
    if kept is None:
        kept, _ = train_stash(cfg, opts, eng, seq,
                              jax.tree.leaves(params["layers"])[0].dtype)

    def layer_run(lo, hi):
        if hi - lo == l_s:
            return params["layers"]
        return jax.tree.map(lambda a: a[:, lo:hi], params["layers"])

    # the stage's layers as one scan per run, since a scan keeps the same
    # residuals in every iteration: the first l_s - kept, then the last
    # kept, whose inputs each tick keeps. Sliced here, outside the tick
    # loop, each run's weights are taken for a trial in one slice per tick.
    runs = [(lo, hi, layer_run(lo, hi), named)
            for lo, hi, named in ((0, l_s - kept, False),
                                  (l_s - kept, l_s, True)) if hi > lo]

    def embed_slot(slot):
        k, m = _slot_ids(eng, slot)
        tok = _take2({"t": tokens}, k, m)["t"]
        emb_k = _take1(params["embed"], k)
        if eng.vocab_parallel:
            x = vp_embed(cfg, eng, emb_k, tok, pos_train, cdt)
        else:
            x = plain_embed(cfg, eng, emb_k, tok, pos_train, cdt)
        if "frontend_embeds" in batch:
            fe = _take2({"f": batch["frontend_embeds"]}, k, m)["f"]
            nf = fe.shape[1]
            x = x.at[:, :nf].set(fe.astype(x.dtype))
        return x

    def slot_pos(slot):
        if cfg.rope == "mrope":
            k, m = _slot_ids(eng, slot)
            return _take2({"p": batch["mrope_pos"]}, k, m)["p"]  # (3, mb, seq)
        return pos_train

    def tick_compute(x_cur, t):
        """One tick's compute (embed + stage + head-loss). Rematerialized
        with the stash ``train_stash`` sizes: with per-layer checkpoints
        (``eng.layer_remat``), the inputs of as many of the stage's last
        layers as fit in HBM and the stage output are kept per tick, so the
        backward replays only the other layers before each layer replays
        itself; with none kept, only the carried activation is,
        n_ticks × (mb, seq, d) — the difference between fitting 16 GB HBM
        and not for large models (see EXPERIMENTS §Perf).
        The ppermute stays OUTSIDE so backward replays compute, not comms
        beyond what AD itself requires.

        skip_bubbles: fill/drain ticks take the cheap cond branch instead of
        computing-then-masking. Safe in SPMD because each cond predicate is
        uniform across every mesh axis its branch communicates over: the
        stage-compute branch only gathers over 'data' (validity depends on
        (t, stage) only); the embed/head branches psum over 'model' (validity
        depends on t only)."""
        # --- inject (stage 0's input for slot t) --------------------------
        valid_in = t < eng.n_slots
        if eng.skip_bubbles:
            x_emb = lax.cond(valid_in, embed_slot,
                             lambda _: jnp.zeros((mb, seq, d), cdt), t)
        else:
            x_emb = embed_slot(t)
        x_in = jnp.where(s_idx == 0, x_emb, x_cur)
        # --- stage compute -------------------------------------------------
        slot_cur = t - s_idx
        valid_cur = (slot_cur >= 0) & (slot_cur < eng.n_slots)
        k_cur, _ = _slot_ids(eng, slot_cur)
        x_in = jnp.where(valid_cur, x_in, 0.0).astype(cdt)

        def run_stage(x_in):
            shared = (_take1(params["shared"], k_cur)
                      if "shared" in params else None)
            y, aux = x_in, 0.0
            for lo, hi, p_run, named in runs:
                y, _, aux_run = lm.stack_apply(
                    cfg, opts, _take1(p_run, k_cur), y,
                    pos=slot_pos(slot_cur), mode="train",
                    shared_params=shared, layer_mask=layer_mask[lo:hi],
                    layer_offset=layer_offset + lo, window=0,
                    layer_param_fn=gather_fn, inner_remat=eng.layer_remat,
                    name_inputs=named)
                aux = aux + aux_run
            return checkpoint_name(y, STAGE_OUTPUT), aux

        if eng.skip_bubbles:
            y, aux = lax.cond(valid_cur, run_stage,
                              lambda x: (x, jnp.zeros((), jnp.float32)),
                              x_in)
        else:
            y, aux = run_stage(x_in)
        aux_val = jnp.where(valid_cur, aux, 0.0)
        # --- head / loss (slot finishing at the last stage) ---------------
        slot_out = t - (S - 1)
        valid_out = (slot_out >= 0) & (slot_out < eng.n_slots)
        k_out, m_out = _slot_ids(eng, slot_out)

        def run_head(y):
            y_last = lax.psum(
                jnp.where(s_idx == S - 1, y, 0.0), eng.stage_axis)
            lbl = _take2({"l": labels}, k_out, m_out)["l"]
            norm_k = _take1({"n": params["final_norm"]}, k_out)["n"]
            head_k = _take1({"h": params["head"]}, k_out)["h"]
            if eng.vocab_parallel:
                return vp_loss(cfg, eng, norm_k, head_k, y_last, lbl)
            return plain_loss(cfg, eng, norm_k, head_k, y_last, lbl)

        if eng.skip_bubbles:
            slot_loss = lax.cond(valid_out, run_head,
                                 lambda _: jnp.zeros((), jnp.float32), y)
        else:
            slot_loss = run_head(y)
        loss_val = jnp.where(valid_out, slot_loss, 0.0)
        return y, loss_val, aux_val

    remat_tick = tick_compute
    if opts.remat:
        policy = None
        if kept:
            policy = jax.checkpoint_policies.save_only_these_names(
                lm.LAYER_INPUT, STAGE_OUTPUT)
        remat_tick = jax.checkpoint(tick_compute, policy=policy)

    def tick(carry, t):
        x_cur, loss_acc, aux_acc = carry
        y, loss_val, aux_val = remat_tick(x_cur, t)
        slot_cur = t - s_idx
        k_cur, _ = _slot_ids(eng, slot_cur)
        k_out, _ = _slot_ids(eng, t - (S - 1))
        aux_acc = aux_acc.at[k_cur].add(aux_val)
        loss_acc = loss_acc.at[k_out].add(loss_val)
        # --- advance the ring ---------------------------------------------
        if S > 1:
            perm = [(i, (i + 1) % S) for i in range(S)]
            x_next = lax.ppermute(y, eng.stage_axis, perm)
        else:
            x_next = y
        return (x_next, loss_acc, aux_acc), None

    x0 = jnp.zeros((mb, seq, d), cdt)
    (xf, loss_acc, aux_acc), _ = lax.scan(
        tick, (x0, jnp.zeros((K,), jnp.float32), jnp.zeros((K,), jnp.float32)),
        jnp.arange(eng.n_ticks))
    # aux was accumulated per stage; total = sum over stages
    aux_acc = lax.psum(aux_acc, eng.stage_axis)
    return loss_acc / M, aux_acc / M


def tickwise_train_grads(cfg: ArchConfig, opts: ModelOptions,
                         eng: EngineConfig, params, batch, objective):
    """A one-stage gang's gradient, each tick's backward right after its
    forward; returns (grads, per-trial loss).

    With one stage every tick embeds its own slot, runs the whole stack
    and takes its own loss, so the step's gradient is the sum of the
    ticks' gradients, each added into its trial's rows. No activation
    outlives its tick, so the tick keeps every layer input it can
    (``train_stash`` with ``eng.stash_ticks`` = 1) and a layer's forward
    runs twice in a step, not three times. ``objective(loss, aux)`` maps
    the per-trial (K,) loss and aux to the scalar being differentiated;
    it must be linear in them, as a sum over ticks. Executes inside
    shard_map, like ``pipeline_train_loss``.
    """
    assert eng.n_stages == 1, "ticks of a pipeline depend on each other"
    K, M = eng.n_trials, eng.n_microbatches
    one = dataclasses.replace(eng, n_trials=1, n_microbatches=1)
    seq = batch["tokens"].shape[-1]
    kept, _ = train_stash(cfg, opts, eng, seq,
                          jax.tree.leaves(params["layers"])[0].dtype)

    def tick(carry, t):
        grads, loss_acc = carry
        k, m = _slot_ids(eng, t)
        p_k = jax.tree.map(lambda a: lax.dynamic_slice_in_dim(a, k, 1),
                           params)
        b_km = jax.tree.map(lambda a: lax.dynamic_slice_in_dim(
            lax.dynamic_slice_in_dim(a, k, 1), m, 1, axis=1), batch)

        def slot_objective(p):
            loss, aux = pipeline_train_loss(cfg, opts, one, p, b_km,
                                            stash_layers=kept)
            loss, aux = loss / M, aux / M
            at_k = jnp.zeros((K,), jnp.float32).at[k]
            return objective(at_k.set(loss[0]), at_k.set(aux[0])), loss[0]

        g, loss = jax.grad(slot_objective, has_aux=True)(p_k)
        grads = jax.tree.map(
            lambda a, gk: lax.dynamic_update_slice_in_dim(
                a, lax.dynamic_slice_in_dim(a, k, 1) + gk, k, 0), grads, g)
        return (grads, loss_acc.at[k].add(loss)), None

    zeros = (jax.tree.map(jnp.zeros_like, params),
             jnp.zeros((K,), jnp.float32))
    (grads, loss_vec), _ = lax.scan(tick, zeros, jnp.arange(eng.n_slots))
    return grads, loss_vec


# ---------------------------------------------------------------------------
# Train step (grad + reductions + per-trial optimizer update)
# ---------------------------------------------------------------------------


def make_train_step(cfg: ArchConfig, opts: ModelOptions, eng: EngineConfig,
                    mesh, optimizer, jit: bool = True) -> Callable:
    """Builds the jitted multi-trial pipelined train step.

    Returns fn(params, opt_state, batch, hparams, step) ->
    (params, opt_state, metrics). ``hparams`` is a dict of (K,) arrays
    (per-trial learning rates etc. — Hydra's model-selection axis).

    The program is ``jit_train_step``; its operations carry the named
    scopes ``forward`` (the loss; the backward pass is its transpose,
    ``transpose(jvp(forward))``, remat's recompute included),
    ``grad_reduce`` and ``optimizer``, so a device trace splits the step.
    Scopes are metadata: the compiled instructions are the same without
    them (``tests/test_obs.py`` checks this).
    """
    pspecs = param_pspecs(cfg, eng)
    ospecs = optimizer.state_pspecs(pspecs)
    bspecs = batch_pspecs(cfg, eng, train=True)

    def train_step(params, opt_state, batch, hparams, step):
        # objective normalization: grads are psum'd over the data(+pod) axes,
        # so divide the local objective by the DP degree — the CE term then
        # equals the global-batch mean exactly; the MoE aux term is defined
        # per data-shard microbatch (Switch-style) and averaged.
        dp_degree = eng.data_size * eng.pod_size

        def objective(loss_vec, aux_vec):
            total = loss_vec.sum()
            if cfg.moe is not None:
                total = total + cfg.moe.load_balance_coef * aux_vec.sum()
            return total / dp_degree

        def local_loss(p):
            with jax.named_scope("forward"):
                loss_vec, aux_vec = pipeline_train_loss(cfg, opts, eng, p,
                                                        batch)
                return objective(loss_vec, aux_vec), loss_vec

        if eng.n_stages == 1:
            with jax.named_scope("forward"):
                grads, loss_vec = tickwise_train_grads(
                    cfg, opts, eng, params, batch, objective)
        else:
            grads, loss_vec = jax.grad(local_loss, has_aux=True)(params)
        with jax.named_scope("grad_reduce"):
            grads, gnorm = reduce_grads(cfg, eng, grads)
        with jax.named_scope("optimizer"):
            params_new, opt_new = optimizer.update(params, grads, opt_state,
                                                   hparams, step,
                                                   grad_norm=gnorm)
        # per-trial loss averaged over the data(+pod) axes
        for ax in eng.dp_axes:
            loss_vec = lax.pmean(loss_vec, ax)
        metrics = {"loss": loss_vec, "grad_norm": gnorm}
        return params_new, opt_new, metrics

    mapped = jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(pspecs, ospecs, bspecs, P(), P()),
        out_specs=(pspecs, ospecs, {"loss": P(), "grad_norm": P()}),
        check_vma=False)
    if not jit:
        return mapped
    return jax.jit(mapped, donate_argnums=(0, 1))


def reduce_grads(cfg: ArchConfig, eng: EngineConfig, grads):
    """Explicit gradient reductions + per-trial global grad norm.

    Leaves sharded over an axis already carry a *summed* gradient for that
    axis (the all_gather/psum transposes inside AD produce it); replicated
    leaves need an explicit psum. The per-trial norm weights each leaf's
    square-sum once regardless of replication.
    """
    pspecs = param_pspecs(cfg, eng)
    k = eng.n_trials
    # sq-sum accumulators keyed by which axes still shard the (reduced) grad
    acc = {"both": jnp.zeros((k,), jnp.float32),
           "stage": jnp.zeros((k,), jnp.float32),
           "data": jnp.zeros((k,), jnp.float32),
           "none": jnp.zeros((k,), jnp.float32)}

    def one(g, spec):
        axes_in_spec = [a for a in jax.tree.leaves(tuple(spec))
                        if isinstance(a, str)]
        out = g
        if eng.data_axis not in axes_in_spec:
            out = lax.psum(out, eng.data_axis)
        if eng.stage_axis not in axes_in_spec:
            out = lax.psum(out, eng.stage_axis)
        if eng.pod_axis is not None:
            out = lax.psum(out, eng.pod_axis)
        sq = jnp.sum(jnp.square(out.astype(jnp.float32)),
                     axis=tuple(range(1, out.ndim)))
        s_sh = eng.stage_axis in axes_in_spec
        d_sh = eng.data_axis in axes_in_spec
        key = ("both" if s_sh and d_sh else "stage" if s_sh
               else "data" if d_sh else "none")
        acc[key] = acc[key] + sq
        return out

    flat_g, treedef = jax.tree.flatten(grads)
    flat_s = treedef.flatten_up_to(pspecs)
    out = [one(g, s) for g, s in zip(flat_g, flat_s)]
    total = (lax.psum(acc["both"], (eng.stage_axis, eng.data_axis))
             + lax.psum(acc["stage"], eng.stage_axis)
             + lax.psum(acc["data"], eng.data_axis)
             + acc["none"])
    gnorm = jnp.sqrt(total)
    return jax.tree.unflatten(treedef, out), gnorm


# ---------------------------------------------------------------------------
# Serving: pipelined prefill / decode (forward-only, KV/SSM cache threading)
# ---------------------------------------------------------------------------


def shared_slots_per_stage(cfg: ArchConfig, plan: StagePlan) -> int:
    """Uniform (max) shared-attention site count per stage (SPMD padding)."""
    if cfg.hybrid is None:
        return 0
    return max(lm.n_shared_sites(cfg, plan.layer_offset(s),
                                 plan.layers_per_stage)
               for s in range(plan.n_stages))


def _check_paged_support(cfg: ArchConfig, eng: EngineConfig) -> None:
    if cfg.family in ("ssm", "hybrid") or cfg.hybrid is not None:
        raise ValueError(
            "paged KV-cache supports attention-family archs only (SSM/conv "
            "states are O(1) per row and have nothing to page)")
    if eng.n_blocks < 1:
        raise ValueError("paged serving needs n_blocks >= 1 "
                         "(see scheduler.plan_serve_capacity)")
    dp = 1 if eng.batch_replicated else eng.data_size * eng.pod_size
    if eng.n_blocks % dp:
        raise ValueError(f"n_blocks={eng.n_blocks} must divide evenly over "
                         f"the {dp} data-parallel pool partitions")


def serve_cache_struct(cfg: ArchConfig, eng: EngineConfig,
                       dry_run: bool = True, mesh=None):
    """Global cache pytree (ShapeDtypeStructs) for the serving pipeline;
    ``dry_run=False`` gives the zero-filled cache itself, written straight
    into its ``serve_cache_pspecs`` sharding over ``mesh`` when one is given
    (the sharding the serve step returns it in, so the first call compiles
    the same program as every later one).

    Dense layout: layer leaves (K, M, Lp, mb_global, ...) with Lp sharded
    over the stage axis; shared-site leaves (K, M, S*slots, mb_global, ...).
    Paged layout (``eng.paged``): one block *pool* per (trial, layer) shared
    by every slot cell — leaves (K, Lp, n_blocks, h_kv, block_size, hd),
    head-major (``blocks.layer_cache_shape``), with the n_blocks axis
    sharded over the data/pod axes (each shard's rows reach only its own
    pool slice, via local ids in the block tables).
    """
    plan = plan_stages(cfg, eng.n_stages)
    if eng.paged:
        _check_paged_support(cfg, eng)
        one = BLK.layer_cache_shape(cfg, eng.n_blocks, 0, eng.cache_dtype,
                                    block_size=eng.block_size)
        layers = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (eng.n_trials, plan.padded_layers) + s.shape, s.dtype), one)
        tree = {"layers": layers, "shared": None}
        return tree if dry_run else _zeros(tree, cfg, eng, mesh)
    mb_global = eng.microbatch * (1 if eng.batch_replicated
                                  else eng.data_size * eng.pod_size)
    one = BLK.layer_cache_shape(cfg, mb_global, eng.max_seq, eng.cache_dtype)
    lead = (eng.n_trials, eng.cache_groups, plan.padded_layers)
    layers = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(lead + s.shape, s.dtype), one)
    shared = None
    if cfg.hybrid is not None:
        s_one = BLK.shared_cache_shape(cfg, mb_global, eng.max_seq,
                                       eng.cache_dtype, eng.window)
        n_slots = eng.n_stages * shared_slots_per_stage(cfg, plan)
        shared = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (eng.n_trials, eng.cache_groups, n_slots) + s.shape,
                s.dtype), s_one)
    tree = {"layers": layers, "shared": shared}
    return tree if dry_run else _zeros(tree, cfg, eng, mesh)


def _zeros(tree, cfg: ArchConfig, eng: EngineConfig, mesh):
    if mesh is None:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tree)
    return jax.tree.map(
        lambda s, p: jnp.zeros(s.shape, s.dtype,
                               device=NamedSharding(mesh, p)),
        tree, serve_cache_pspecs(cfg, eng))


def serve_cache_pspecs(cfg: ArchConfig, eng: EngineConfig):
    st = eng.stage_axis
    batch_ax = None if eng.batch_replicated else eng.dp_axes
    if eng.paged:
        # pool: layers over stages, blocks over the data/pod axes
        spec = P(None, st, batch_ax, None, None, None)
        return {"layers": {"k": spec, "v": spec}, "shared": None}
    plan = plan_stages(cfg, eng.n_stages)
    one = BLK.layer_cache_shape(cfg, 1, max(eng.max_seq, 1), eng.cache_dtype)
    layers = jax.tree.map(
        lambda s: P(None, None, st, batch_ax, *([None] * (len(s.shape) - 1))),
        one)
    shared = None
    if cfg.hybrid is not None:
        s_one = BLK.shared_cache_shape(cfg, 1, max(eng.max_seq, 1),
                                       eng.cache_dtype, eng.window)
        shared = jax.tree.map(
            lambda s: P(None, None, st, batch_ax,
                        *([None] * (len(s.shape) - 1))), s_one)
    return {"layers": layers, "shared": shared}


def pipeline_serve(cfg: ArchConfig, opts: ModelOptions, eng: EngineConfig,
                   params, cache, batch, mode: str):
    """Pipelined forward for serving; runs inside shard_map.

    decode: batch = {tokens (K,M,mb,1), positions (K,M,mb)}; one new token per
    sequence against the live cache.
    prefill: batch = {tokens (K,M,mb,seq)} (+ frontend extras); fills the
    cache and emits the first generated token.
    append: batch = {tokens (K,M,mb,qlen), positions (K,M,mb)}; inserts qlen
    tokens per row starting at the row's own cache depth ``positions`` —
    the continuous-batching admission path (chunked prefill of new requests
    into recycled slots, per-row ragged offsets). The K axis is the
    co-serving axis: every slot tick indexes params, cache slices, and block
    tables by its own trial k, so one call advances cells of K different
    model variants at once.
    mixed: append plus ``batch["qlens"]`` (K,M,mb) int32 per-row real query
    counts — one fused tick advancing prefill chunks (qlen = chunk width)
    AND decode rows (qlen = 1) AND idle rows (qlen = 0) in a single ragged
    wave padded to the wave max. Padded positions are never written to the
    cache and attend to nothing; the head samples each row at its own last
    real position (qlens - 1) instead of the trailing column.
    verify: mixed's ragged-append semantics with a per-POSITION head readout
    — ``tokens_out``/``logit_max`` come back (K,M,mb,qlen), holding each
    row's greedy argmax at every query position instead of only the last.
    Position i's token is what decode at that depth would emit (same key
    set, masked scores contribute exactly 0), which is the speculative-
    decoding contract: the target verifies a drafter's gamma proposals plus
    its own bonus token in one call. Outputs at positions >= a row's qlens
    are garbage (clamped padding) — callers slice by qlens.
    All modes accept an optional ``batch["active"]`` (K,M,mb) bool row mask:
    inactive rows compute (SPMD shapes are static) but their cache rows are
    left untouched, so idle slots can ride along in a live batch.
    ``eng.paged`` (append/decode only): the cache holds per-layer block pools
    and batch additionally carries ``block_tables`` (K,M,mb,max_blocks) int32
    local physical ids; K/V writes scatter through the tables and reads
    gather each row's logical view (blocks.paged_kv_update), so the live HBM
    cache footprint is the pool, not slots × max_seq.
    Returns (new_cache, tokens_out (K,M,mb), logit_max (K,M,mb)).
    """
    if eng.paged and mode not in ("append", "decode", "mixed", "verify"):
        raise ValueError(f"paged serving supports append/decode/mixed/verify "
                         f"only, got mode={mode!r}")
    S = eng.n_stages
    K, M = eng.n_trials, eng.n_microbatches
    plan = plan_stages(cfg, S)
    l_s = plan.layers_per_stage
    s_idx = lax.axis_index(eng.stage_axis)
    layer_offset = s_idx * l_s
    layer_mask = (layer_offset + jnp.arange(l_s)) < cfg.n_layers
    gather_fn = make_layer_gather(cfg, eng)
    n_sh = shared_slots_per_stage(cfg, plan)

    tokens = batch["tokens"]
    mb, qlen = tokens.shape[-2], tokens.shape[-1]
    cdt = opts.compute_dtype
    nc = eng.prefill_chunks if (mode == "prefill"
                                and eng.prefill_chunks > 1) else 1
    ragged = mode in ("append", "mixed", "verify")
    stack_mode = "append" if (nc > 1 or ragged) else mode
    active = batch.get("active")
    qlens = batch.get("qlens") if mode in ("mixed", "verify") else None

    def chunk_of(m):
        return m % nc if nc > 1 else jnp.zeros((), jnp.int32)

    def slot_rows_active(k, m):
        if active is None:
            return None
        return _take2({"a": active}, k, m)["a"]  # (mb,) bool

    def embed_slot(slot):
        k, m = _slot_ids(eng, slot)
        tok = _take2({"t": tokens}, k, m)["t"]
        if mode == "decode":
            pos = _take2({"p": batch["positions"]}, k, m)["p"][:, None]
        elif ragged:
            pos = slot_pos(slot)  # (mb, qlen) per-row absolute positions
        else:
            pos = chunk_of(m) * qlen + jnp.broadcast_to(
                jnp.arange(qlen), (mb, qlen))
        emb_k = _take1(params["embed"], k)
        if eng.vocab_parallel:
            x = vp_embed(cfg, eng, emb_k, tok, pos, cdt)
        else:
            x = plain_embed(cfg, eng, emb_k, tok, pos, cdt)
        if mode == "prefill" and "frontend_embeds" in batch:
            fe = _take2({"f": batch["frontend_embeds"]}, k, m)["f"]
            x = x.at[:, :fe.shape[1]].set(fe.astype(x.dtype))
        return x

    def slot_pos(slot):
        k, m = _slot_ids(eng, slot)
        if mode == "decode":
            p = _take2({"p": batch["positions"]}, k, m)["p"][:, None]  # (mb,1)
            if cfg.rope == "mrope":
                return jnp.broadcast_to(p, (3, mb, 1))
            return p
        if ragged:
            start = _take2({"p": batch["positions"]}, k, m)["p"]
            pos = start[:, None] + jnp.arange(qlen)[None, :]
            if qlens is not None:
                # clamp padded positions to the row's last real one — they
                # are compute-only (writes dropped, outputs discarded) but
                # must stay inside any position-table/rope range
                ql = _take2({"q": qlens}, k, m)["q"]
                pos = jnp.minimum(
                    pos, (start + jnp.maximum(ql - 1, 0))[:, None])
            return pos
        if cfg.rope == "mrope":
            return _take2({"p": batch["mrope_pos"]}, k, m)["p"]
        return chunk_of(m) * qlen + jnp.broadcast_to(
            jnp.arange(qlen), (mb, qlen))

    def slot_cache(cache, k, m):
        """Local (L_s, ...) cache slice of one slot (+ local shared sites).
        Chunked prefill: the nc chunk-slots of a request group share one
        cache (group = m // nc); chunk order through the pipeline guarantees
        chunk c's write lands at each stage before chunk c+1 reads it."""
        g = m // nc if nc > 1 else m
        lay = _take2(cache["layers"], k, g)
        sh = None
        if cache["shared"] is not None:
            sh = _take2(cache["shared"], k, g)
        return {"layers": lay, "shared": sh}

    def put_cache(cache, k, m, new_slice, valid, row_mask=None):
        m = m // nc if nc > 1 else m

        def upd(buf, new):
            old = lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(buf, k, 0, keepdims=False),
                m, 0, keepdims=False)
            keep = valid
            if row_mask is not None:
                # cache slices are (L_s|sites, mb, ...): rows live on axis 1
                keep = (valid & row_mask).reshape(
                    (1, row_mask.shape[0]) + (1,) * (new.ndim - 2))
            val = jnp.where(keep, new.astype(buf.dtype), old)
            return lax.dynamic_update_slice(
                buf, val[None, None],
                (k, m) + (0,) * (buf.ndim - 2))
        out = {"layers": jax.tree.map(upd, cache["layers"],
                                      new_slice["layers"])}
        if cache["shared"] is not None:
            out["shared"] = jax.tree.map(upd, cache["shared"],
                                         new_slice["shared"])
        else:
            out["shared"] = None
        return out

    def tick(carry, t):
        x_cur, cache, tok_out, val_out = carry
        valid_in = t < eng.n_slots
        if eng.skip_bubbles:
            x_emb = lax.cond(
                valid_in, embed_slot,
                lambda _: jnp.zeros((mb, qlen, cfg.d_model), cdt), t)
        else:
            x_emb = embed_slot(t)
        x_in = jnp.where(s_idx == 0, x_emb, x_cur)
        slot_cur = t - s_idx
        valid_cur = (slot_cur >= 0) & (slot_cur < eng.n_slots)
        k_cur, m_cur = _slot_ids(eng, slot_cur)
        x_in = jnp.where(valid_cur, x_in, 0.0).astype(cdt)

        def run_stage(operand):
            x_in, cache = operand
            p_layers = _take1(params["layers"], k_cur)
            shared = (_take1(params["shared"], k_cur)
                      if "shared" in params else None)
            kv_off = None
            if mode == "decode" or ragged:
                kv_off = _take2({"p": batch["positions"]}, k_cur, m_cur)["p"]
            elif nc > 1:
                kv_off = jnp.full((mb,), chunk_of(m_cur) * qlen, jnp.int32)
            ql_cur = None
            if qlens is not None:
                ql_cur = _take2({"q": qlens}, k_cur, m_cur)["q"]
            if eng.paged:
                # the pool is shared across slots: slice per trial only, and
                # gate writes (idle rows, bubble ticks) inside the scatter —
                # a where-style masked write-back would race rows that share
                # the pool leaf
                rows = slot_rows_active(k_cur, m_cur)
                wm = jnp.broadcast_to(valid_cur, (mb,))
                if rows is not None:
                    wm = wm & rows
                c_slice = {"layers": _take1(cache["layers"], k_cur),
                           "shared": None}
                bt = _take2({"b": batch["block_tables"]}, k_cur, m_cur)["b"]
                y, c_new, _ = lm.stack_apply(
                    cfg, opts, p_layers, x_in, pos=slot_pos(slot_cur),
                    mode=stack_mode, cache=c_slice, shared_params=shared,
                    layer_mask=layer_mask, layer_offset=layer_offset,
                    kv_offset=kv_off, window=eng.window,
                    layer_param_fn=gather_fn, block_tables=bt, write_mask=wm,
                    q_lens=ql_cur)
                new_layers = jax.tree.map(
                    lambda buf, new: lax.dynamic_update_slice(
                        buf, new[None].astype(buf.dtype),
                        (k_cur,) + (0,) * (buf.ndim - 1)),
                    cache["layers"], c_new["layers"])
                return y, {"layers": new_layers, "shared": None}
            c_slice = slot_cache(cache, k_cur, m_cur)
            y, c_new, _ = lm.stack_apply(
                cfg, opts, p_layers, x_in, pos=slot_pos(slot_cur),
                mode=stack_mode, cache=c_slice, shared_params=shared,
                layer_mask=layer_mask, layer_offset=layer_offset,
                kv_offset=kv_off, window=eng.window,
                layer_param_fn=gather_fn, q_lens=ql_cur)
            return y, put_cache(cache, k_cur, m_cur, c_new, valid_cur,
                                slot_rows_active(k_cur, m_cur))

        if eng.skip_bubbles:
            y, cache = lax.cond(valid_cur, run_stage,
                                lambda op: (op[0], op[1]), (x_in, cache))
        else:
            y, cache = run_stage((x_in, cache))
        # head: greedy next token for the slot draining at the last stage
        slot_out = t - (S - 1)
        valid_out = (slot_out >= 0) & (slot_out < eng.n_slots)
        k_out, m_out = _slot_ids(eng, slot_out)
        norm_k = _take1({"n": params["final_norm"]}, k_out)["n"]
        head_k = _take1({"h": params["head"]}, k_out)["h"]
        if mode == "verify":
            # speculative verify: greedy argmax at EVERY query position —
            # the drafter's proposals and the target's bonus token are all
            # judged from one call (outputs past a row's qlens are clamped
            # padding; the engine slices by qlens)
            y_all = lax.psum(jnp.where(s_idx == S - 1, y, 0.0),
                             eng.stage_axis)
            if eng.vocab_parallel:
                nxt, lmax = vp_greedy_tokens(cfg, eng, norm_k, head_k, y_all)
            else:
                x_h = lm.final_norm_apply(cfg, norm_k, y_all)
                logits = jnp.einsum("bsd,dv->bsv", x_h, head_k)
                nxt, lmax = jnp.argmax(logits, -1), jnp.max(logits, -1)
            idx4 = (k_out, m_out, 0, 0)
        else:
            if qlens is not None:
                # mixed ragged wave: each row's chunk ends at its own
                # qlens - 1, not the padded trailing column
                ql_out = _take2({"q": qlens}, k_out, m_out)["q"]
                sel = jnp.clip(ql_out - 1, 0, qlen - 1)[:, None, None]
                y_head = jnp.take_along_axis(y, sel, axis=1)
            else:
                y_head = y[:, -1:]
            y_last = lax.psum(jnp.where(s_idx == S - 1, y_head, 0.0),
                              eng.stage_axis)
            if eng.vocab_parallel:
                nxt, lmax = vp_greedy_token(cfg, eng, norm_k, head_k, y_last)
            else:
                x_h = lm.final_norm_apply(cfg, norm_k, y_last)
                logits = jnp.einsum("bsd,dv->bsv", x_h, head_k)[:, 0]
                nxt, lmax = jnp.argmax(logits, -1), jnp.max(logits, -1)
            idx4 = (k_out, m_out, 0)
        upd_tok = jnp.where(valid_out, nxt.astype(jnp.int32),
                            lax.dynamic_index_in_dim(
                                lax.dynamic_index_in_dim(
                                    tok_out, k_out, 0, False), m_out, 0,
                                False))
        tok_out = lax.dynamic_update_slice(
            tok_out, upd_tok[None, None], idx4)
        upd_val = jnp.where(valid_out, lmax.astype(jnp.float32),
                            lax.dynamic_index_in_dim(
                                lax.dynamic_index_in_dim(
                                    val_out, k_out, 0, False), m_out, 0,
                                False))
        val_out = lax.dynamic_update_slice(
            val_out, upd_val[None, None], idx4)
        if S > 1:
            perm = [(i, (i + 1) % S) for i in range(S)]
            x_next = lax.ppermute(y, eng.stage_axis, perm)
        else:
            x_next = y
        return (x_next, cache, tok_out, val_out), None

    x0 = jnp.zeros((mb, qlen, cfg.d_model), cdt)
    out_shape = (K, M, mb, qlen) if mode == "verify" else (K, M, mb)
    tok0 = jnp.zeros(out_shape, jnp.int32)
    val0 = jnp.zeros(out_shape, jnp.float32)
    (xf, cache, tok_out, val_out), _ = lax.scan(
        tick, (x0, cache, tok0, val0), jnp.arange(eng.n_ticks))
    return cache, tok_out, val_out


def make_serve_step(cfg: ArchConfig, opts: ModelOptions, eng: EngineConfig,
                    mesh, mode: str, jit: bool = True,
                    with_active: bool = False, tracer=None) -> Callable:
    """Builds the jitted pipelined serving step.

    ``mode``: prefill | decode | append | mixed | verify. ``append`` is the
    continuous-batching admission step: qlen tokens per row inserted at
    per-row cache depths (batch carries ``positions`` start offsets).
    ``mixed`` is the fused-admission tick: append semantics plus a (K,M,mb)
    int32 ``qlens`` batch entry giving each row's real query count (chunk
    width / 1 for decode / 0 for idle), so one program advances prefill and
    decode rows together. ``verify`` is the speculative-decoding target
    call: mixed's ragged append with a per-position head readout — tokens
    and logit_max come back (K,M,mb,qlen). ``with_active=True`` adds a
    (K,M,mb) bool ``active`` row mask to the batch: inactive rows never touch
    their cache (the serve engine uses it to let idle/decoding slots ride
    along during admission and vice versa).
    ``tracer`` (an *enabled* ``repro.obs.Tracer``) wraps the step to emit a
    ``compile`` event on the first call of each (token qlen, block-table
    width) shape signature in this engine — the signatures JAX traces
    anew. It does not mark an XLA compile: JAX's caches may already hold
    the program (the tracer's ``xla_compile`` events are the real
    compiles). Pass None (not a NullTracer) when tracing is off: the
    returned step is then the bare jitted fn with zero wrapper overhead.
    The program is ``jit_serve_<mode>``.
    Returns fn(params, cache, batch) -> (new_cache, tokens, logit_max).
    """
    if mode in ("append", "mixed", "verify") and cfg.rope == "mrope":
        raise ValueError("append mode (continuous batching) does not support "
                         "mrope archs; use the static prefill path")
    if mode in ("mixed", "verify") and cfg.family in ("ssm", "hybrid"):
        raise ValueError("mixed-tick/verify serving is attention-family "
                         "only: ragged padded tokens would advance "
                         "recurrent SSM state")
    pspecs = param_pspecs(cfg, eng)
    bspecs = batch_pspecs(cfg, eng, train=False)
    if mode == "prefill":
        bspecs.pop("positions", None)
    else:  # decode/append consume plain tokens; modality prefixes live in
        # the cache (written by a static prefill)
        bspecs.pop("frontend_embeds", None)
        bspecs.pop("mrope_pos", None)
    if mode in ("mixed", "verify"):
        bspecs["qlens"] = P(None, None,
                            None if eng.batch_replicated else eng.dp_axes)
    if with_active:
        bspecs["active"] = P(None, None,
                             None if eng.batch_replicated else eng.dp_axes)
    if eng.paged:
        # (K, M, mb_global, max_blocks) local physical ids, rows sharded
        # with the batch so each shard sees only tables into its pool slice
        bspecs["block_tables"] = P(
            None, None, None if eng.batch_replicated else eng.dp_axes, None)
    cspecs = serve_cache_pspecs(cfg, eng)
    if mode == "verify":  # per-position outputs carry a trailing qlen axis
        batch_ax = (P() if eng.batch_replicated
                    else P(None, None, eng.dp_axes, None))
    else:
        batch_ax = P() if eng.batch_replicated else P(None, None, eng.dp_axes)

    def serve(params, cache, batch):
        return pipeline_serve(cfg, opts, eng, params, cache, batch, mode)

    serve.__name__ = serve.__qualname__ = f"serve_{mode}"
    mapped = jax.shard_map(
        serve, mesh=mesh,
        in_specs=(pspecs, cspecs, bspecs),
        out_specs=(cspecs, batch_ax, batch_ax),
        check_vma=False)
    fn = jax.jit(mapped, donate_argnums=(1,)) if jit else mapped
    if tracer is None or not tracer.enabled:
        return fn
    seen: set = set()

    def traced(params, cache, batch):
        bt = batch.get("block_tables")
        key = (int(batch["tokens"].shape[-1]),
               int(bt.shape[-1]) if bt is not None else 0)
        if key not in seen:
            seen.add(key)
            tracer.compile(mode, qlen=key[0], table_width=key[1])
        return fn(params, cache, batch)

    return traced


def make_slot_reset(cfg: ArchConfig, eng: EngineConfig, mesh,
                    jit: bool = True) -> Callable:
    """Builds fn(cache, mask) zeroing the cache rows of recycled slots.

    ``mask``: (K, cache_groups, mb_global) bool — True rows are cleared the
    tick their request finishes, before a queued request is admitted into the
    freed slot. KV rows beyond kv_len are never attended, but SSM/conv states
    are recurrent and MUST restart from zero for the next request.
    (Paged engines never call this: paged serving is attention-only, stale
    pool blocks are masked by kv_len, and freed blocks return to the
    allocator host-side.)
    """
    if eng.paged:
        raise ValueError("paged caches need no slot reset (no recurrent "
                         "state; stale blocks are masked via kv_len)")
    cspecs = serve_cache_pspecs(cfg, eng)
    mspec = P(None, None, None if eng.batch_replicated else eng.dp_axes)

    def slot_reset(cache, mask):
        def zero(buf):
            mk = mask.reshape(mask.shape[:2] + (1, mask.shape[2])
                              + (1,) * (buf.ndim - 4))
            return jnp.where(mk, jnp.zeros((), buf.dtype), buf)

        return {"layers": jax.tree.map(zero, cache["layers"]),
                "shared": (jax.tree.map(zero, cache["shared"])
                           if cache["shared"] is not None else None)}

    mapped = jax.shard_map(slot_reset, mesh=mesh, in_specs=(cspecs, mspec),
                           out_specs=cspecs, check_vma=False)
    if not jit:
        return mapped
    return jax.jit(mapped, donate_argnums=(0,))


@dataclasses.dataclass
class TransferKernels:
    """The three block-movement primitives consumed by
    ``serve.transfer.TransferEngine`` (the sole caller — block movement has
    no one-shot public API; every copy/swap is enqueued on the transfer
    engine and batched per engine round)."""

    copy: Callable  # (cache, src, dst) -> cache; compiled pool copy
    extract: Callable  # (cache, k, shard, local_ids) -> [payload, ...]
    inject: Callable  # (cache, k, shard, local_ids, payloads) -> cache


def make_transfer_kernels(cfg: ArchConfig, eng: EngineConfig, mesh,
                          jit: bool = True) -> TransferKernels:
    """Builds the device kernels behind the serve transfer engine.

    **copy(cache, src, dst)** — batched device pool copy dst := src per
    layer, the copy-on-write half of prefix sharing: before a row may write
    into a partially-matched *shared* block (refcount > 1), the engine forks
    it — allocates a private block and copies the shared block's K/V rows
    into it, so no shared block is ever mutated. ``src``/``dst`` are
    (K, dp, n_copies) int32 *local* physical ids per (trial, data-shard)
    pool partition, -1 = no-op padding; a block id addresses the same slot
    of every layer's pool leaf, so one call moves all layers.

    **extract(cache, k, shard, local_ids)** — device → host: read trial k /
    shard's pool blocks out to one host payload per id (a (2, Lp, h_kv,
    block_size, hd) array stacking K and V). Read-only — extracting a
    shared block is always safe — and eager: spill/retract callers free the
    device block immediately after.

    **inject(cache, k, shard, local_ids, payloads)** — host → device: write
    extracted payloads back into (freshly allocated) pool blocks. Inverse
    of extract; round-trips bit-exactly.

    Extraction/injection address the *global* pool leaf (the n_blocks axis
    concatenates the dp shards), so local ids are offset by the shard's
    slice before indexing.
    """
    _check_paged_support(cfg, eng)
    cspecs = serve_cache_pspecs(cfg, eng)
    ispec = P(None, None if eng.batch_replicated else eng.dp_axes, None)

    def transfer_copy(cache, src, dst):
        s, d = src[:, 0], dst[:, 0]  # local shard: (K, n_copies)

        def upd(buf):  # (K, Lp_local, nb_local, h_kv, bs, hd)
            nb = buf.shape[2]

            def one(bufk, sk, dk):
                vals = jnp.take(bufk, jnp.clip(sk, 0, nb - 1), axis=1)
                dk = jnp.where((sk >= 0) & (dk >= 0), dk, nb)  # OOB: dropped
                return bufk.at[:, dk].set(vals, mode="drop")

            return jax.vmap(one)(buf, s, d)

        return {"layers": jax.tree.map(upd, cache["layers"]), "shared": None}

    mapped = jax.shard_map(transfer_copy, mesh=mesh,
                           in_specs=(cspecs, ispec, ispec),
                           out_specs=cspecs, check_vma=False)
    copy_fn = jax.jit(mapped, donate_argnums=(0,)) if jit else mapped

    dp = 1 if eng.batch_replicated else eng.data_size * eng.pod_size
    per_shard = max(eng.n_blocks // dp, 1)

    def _gids(shard, local_ids):
        return np.asarray([shard * per_shard + i for i in local_ids],
                          np.int32)

    def extract(cache, k, shard, local_ids):
        gids = _gids(shard, local_ids)
        # advanced indices (k, gids) split by the layer slice: result is
        # (n, Lp, h_kv, block_size, hd)
        kv = np.asarray(cache["layers"]["k"][k, :, gids])
        vv = np.asarray(cache["layers"]["v"][k, :, gids])
        return [np.stack([kv[j], vv[j]]) for j in range(len(local_ids))]

    def inject(cache, k, shard, local_ids, payloads):
        gids = _gids(shard, local_ids)
        pk = jnp.asarray(np.stack([p[0] for p in payloads]))
        pv = jnp.asarray(np.stack([p[1] for p in payloads]))
        lk = cache["layers"]["k"].at[k, :, gids].set(pk)
        lv = cache["layers"]["v"].at[k, :, gids].set(pv)
        return {"layers": {"k": lk, "v": lv}, "shared": None}

    return TransferKernels(copy=copy_fn, extract=extract, inject=inject)


def batch_pspecs(cfg: ArchConfig, eng: EngineConfig, train: bool):
    """PartitionSpecs for the (K, M, batch, ...) slot-major batch arrays."""
    dp = P(None, None, None if eng.batch_replicated else eng.dp_axes)
    specs = {"tokens": dp}
    if train:
        specs["labels"] = dp
    else:
        specs["positions"] = dp
    if cfg.frontend is not None:
        specs["frontend_embeds"] = dp
    if cfg.rope == "mrope":
        # (K, M, 3, mb, seq): batch dim is 3rd
        specs["mrope_pos"] = P(None, None, None,
                               None if eng.batch_replicated else eng.dp_axes)
    return specs
