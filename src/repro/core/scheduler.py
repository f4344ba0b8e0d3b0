"""Hydra's shard-parallel task scheduler (the paper's "scheduler" box).

On TPU the *within-gang* schedule is compiled (static round-robin — see
pipeline.py); this module handles everything the compiler can't:

  * **capacity planning** — how many concurrent trials K fit per chip given
    the HBM budget (params + optimizer + pipeline activation stash + caches);
  * **gang planning** — grouping a trial population (possibly heterogeneous
    architectures) into same-architecture gangs of size ≤ K_max and choosing
    microbatch counts so the pipeline bubble fraction meets a target;
  * **failure / elasticity policy** — re-planning gangs around cordoned mesh
    slices and shrunken data axes (used by runtime/elastic.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.partitioner import plan_stages
from repro.core.pipeline import EngineConfig

# TPU v5e (the deployment target; see EXPERIMENTS.md §Roofline)
HBM_BYTES_PER_CHIP = 16 * 1024 ** 3
HBM_BUDGET_FRACTION = 0.9  # leave headroom for XLA scratch


@dataclasses.dataclass(frozen=True)
class TrialSpec:
    """One model-selection trial (the task-parallel unit of the paper)."""

    arch: str
    lr: float
    weight_decay: float = 0.0
    seed: int = 0
    tag: str = ""


@dataclasses.dataclass(frozen=True)
class MemoryEstimate:
    params_bytes: int
    opt_bytes: int
    act_bytes: int
    cache_bytes: int

    @property
    def total(self) -> int:
        return self.params_bytes + self.opt_bytes + self.act_bytes \
            + self.cache_bytes


def per_chip_bytes(cfg: ArchConfig, eng: EngineConfig, seq_len: int,
                   train: bool, param_bytes: int = 2,
                   opt_bytes_per_param: int = 12) -> MemoryEstimate:
    """Per-chip HBM model for ONE trial under the engine config.

    Stage sharding divides layer params by n_stages; FSDP further divides by
    data_size. The activation stash is the tick stash (``stash_bytes`` with
    no layer kept), the least the train step keeps: the layer inputs it may
    keep besides only fill what the gang leaves of the budget
    (``stash_layers``), so plans are made with the minimum. Optimizer:
    ``opt_bytes_per_param`` (12: fp32 m + v + a master copy; this repo's
    AdamW keeps m and v alone, so 4 B of it is headroom).
    """
    plan = plan_stages(cfg, eng.n_stages)
    layer_p = cfg.layer_param_count() * plan.layers_per_stage
    if eng.fsdp:
        layer_p = math.ceil(layer_p / eng.data_size)
    vocab_p = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    vocab_p = math.ceil(vocab_p / (eng.n_stages if eng.vocab_parallel else 1))
    shared_p = cfg.shared_block_param_count()
    n_params_local = layer_p + vocab_p + shared_p + cfg.d_model
    params_b = n_params_local * param_bytes
    opt_b = n_params_local * opt_bytes_per_param if train else 0
    if train:
        # every tick's stage input, though a one-stage step holds one
        act_b = (stash_bytes(cfg, eng, seq_len, 0, ticks=eng.n_ticks)
                 + _train_transient_bytes(cfg, eng, seq_len))
        cache_b = 0
    else:
        act_b = 4 * eng.microbatch * min(seq_len, 4096) * cfg.d_model * 4
        cache_b = _cache_bytes_per_chip(cfg, eng, seq_len)
    return MemoryEstimate(params_b, opt_b, act_b, cache_b)


def _train_transient_bytes(cfg: ArchConfig, eng: EngineConfig,
                           seq_len: int) -> int:
    """The train step's transient working set: gathered layer weights (×2
    for grad), attn carries, fp32 grad buffers of one layer."""
    return (3 * cfg.layer_param_count() * 4
            + 8 * eng.microbatch * min(seq_len, 4096) * cfg.d_model * 4)


def stash_bytes(cfg: ArchConfig, eng: EngineConfig, seq_len: int,
                kept_layers: int, act_bytes: int = 2,
                ticks: Optional[int] = None) -> int:
    """Per-chip bytes of the train step's activation stash
    (``pipeline_train_loss``) over ``ticks`` ticks (default
    ``eng.stash_ticks``, those the step's backward holds at once), one of
    two schemes per tick:

    * the tick stash (``kept_layers`` 0): each tick keeps its stage input,
      and its backward replays the whole stage to regain the layers'
      inputs before each layer replays itself inside its own checkpoint;
    * the layer stash: each tick also keeps the inputs of its stage's last
      ``kept_layers`` layers and the stage output (its stage input only
      while some layer is not kept), and its backward replays only the
      layers not kept.

    Each is an (mb, seq, d) activation of ``act_bytes``. Empirically (XLA
    buffer dump, EXPERIMENTS §Perf) the backward also materializes an fp32
    convert of a narrower stash hoisted out of the loop, so an element
    below 4 bytes is priced with 4 more (bf16: 6).
    """
    layers = plan_stages(cfg, eng.n_stages).layers_per_stage
    per_tick = kept_layers + 1 + (0 < kept_layers < layers)
    elem = act_bytes if act_bytes >= 4 else act_bytes + 4
    ticks = eng.stash_ticks if ticks is None else ticks
    return ticks * per_tick * eng.microbatch * seq_len * cfg.d_model * elem


def stash_layers(cfg: ArchConfig, eng: EngineConfig, seq_len: int,
                 param_bytes: int = 2, act_bytes: int = 2) -> int:
    """How many layer inputs each tick of the gang's train step keeps
    (``pipeline.train_stash``), the rule that picks the stash scheme.

    With ``eng.layer_remat`` (per-layer checkpoints inside the tick's), the
    most whose stash (``stash_bytes``) fits in ``HBM_BYTES_PER_CHIP ×
    HBM_BUDGET_FRACTION`` beside what the gang holds anyway, counted once
    for the gang: K trials' params, optimizer state and gradients (as wide
    as the params) and the step's transients. None fit: the tick stash.
    Without per-layer checkpoints a kept input would spare nothing: 0.

    The estimate leaves out what the compiler adds around the gang: bf16
    copies of float32 weights for the products, one trial's gradient and
    logits per tick, fragmentation. The optimizer's 4 B of headroom a
    parameter and the budget's 10% cover them where checked: compiled for
    a described v5e, the stashes this picks peak within the compiler's
    limit, float32 and bfloat16 (PERF.md §4).
    """
    if not eng.layer_remat:
        return 0
    one = per_chip_bytes(cfg, eng, seq_len, train=True,
                         param_bytes=param_bytes)
    room = (HBM_BYTES_PER_CHIP * HBM_BUDGET_FRACTION
            - eng.n_trials * (2 * one.params_bytes + one.opt_bytes)
            - _train_transient_bytes(cfg, eng, seq_len))
    layers = plan_stages(cfg, eng.n_stages).layers_per_stage
    return max((k for k in range(layers + 1)
                if stash_bytes(cfg, eng, seq_len, k, act_bytes) <= room),
               default=0)


def kv_token_bytes_per_chip(cfg: ArchConfig, eng: EngineConfig) -> int:
    """K+V bytes ONE cached token costs across this chip's layer slice
    (2 tensors × the engine's cache dtype)."""
    plan = plan_stages(cfg, eng.n_stages)
    itemsize = jnp.dtype(eng.cache_dtype).itemsize
    return (cfg.n_kv_heads * cfg.head_dim * 2 * itemsize
            * plan.layers_per_stage)


def _cache_bytes_per_chip(cfg: ArchConfig, eng: EngineConfig,
                          seq_len: int) -> int:
    if eng.paged:
        # the persistent cache is the block pool, not slots × max_seq strips
        dp = 1 if eng.batch_replicated else eng.data_size * eng.pod_size
        local_blocks = eng.n_blocks // max(dp, 1)
        return (local_blocks * eng.block_size
                * kv_token_bytes_per_chip(cfg, eng))
    plan = plan_stages(cfg, eng.n_stages)
    b_local = eng.microbatch * eng.n_microbatches
    if cfg.family in ("ssm", "hybrid"):
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        itemsize = jnp.dtype(eng.cache_dtype).itemsize
        per_layer = b_local * di * s.d_state * 4  # fp32 state
        per_layer += b_local * (s.d_conv - 1) * di * itemsize
        total = per_layer * plan.layers_per_stage
        if cfg.hybrid is not None:
            w = min(seq_len, eng.window) if eng.window else seq_len
            total += (b_local * w * cfg.n_kv_heads * cfg.head_dim * 2
                      * itemsize)
        return total
    return b_local * seq_len * kv_token_bytes_per_chip(cfg, eng)


def max_concurrent_trials(cfg: ArchConfig, eng: EngineConfig, seq_len: int,
                          train: bool = True) -> int:
    """K_max: how many trials fit per chip (the paper's memory ceiling)."""
    budget = HBM_BYTES_PER_CHIP * HBM_BUDGET_FRACTION
    one = per_chip_bytes(cfg, dataclasses.replace(eng, n_trials=1), seq_len,
                         train).total
    return max(1, int(budget // max(one, 1)))


# ---------------------------------------------------------------------------
# Serving capacity planning (continuous-batching engine)
# ---------------------------------------------------------------------------


def plan_serve_capacity(cfg: ArchConfig, base_eng: EngineConfig,
                        max_seq: int, target_bubble: float = 0.25,
                        max_slots: int = 64, paged: bool = False,
                        expected_seq: Optional[int] = None,
                        block_size: int = 16,
                        hbm_bytes: Optional[int] = None,
                        budget_fraction: float = HBM_BUDGET_FRACTION,
                        mix: Optional[Sequence[tuple]] = None,
                        hit_rate: float = 0.0,
                        overcommit: float = 1.0,
                        host_blocks: int = 0,
                        ) -> EngineConfig:
    """Choose the serving slot grid for one model — or a co-serving gang.

    ``mix`` sizes the grid for a *traffic mix* across a K-variant gang: one
    ``(arrival_weight, expected_seq)`` pair per co-served arch. The returned
    config then carries ``n_trials = len(mix)`` (trial row k serves arch k)
    and every per-trial cost — params, dense strips, paged pools — is
    multiplied by K. ``mix=None`` is the single-arch plan (K=1,
    ``expected_seq`` as the lone expectation).

    Dense path: serving is forward-only, so ``per_chip_bytes(train=False)``
    applies — the KV/SSM cache at ``max_seq`` is the marginal HBM cost per
    slot (admission is by *worst case*: every cell reserves a full strip).
    Start from the pipeline-bubble target ((S-1)/(K·M+S-1) <= target —
    more slots = more concurrent requests = smaller bubble, Hydra's
    slot-filling insight applied to serving), then shrink M until the cache
    fits the budget.

    Paged path (``paged=True``): the leftover budget becomes one block pool
    per (chip, trial), and M is sized so the pools back K × M × microbatch
    rows at their arrival-weighted *expected* lengths — admission by
    expectation instead of worst case, which is where the capacity win over
    the dense plan comes from. Each trial's pool is an equal slice (the
    cache leaf is uniform over K); arches whose weighted demand
    ``K · w_k · expected_k`` exceeds the slice lean on the batcher's
    per-arch backpressure at runtime. The returned config carries
    ``n_blocks`` (per trial) / ``block_size``; the runtime batcher keeps the
    plan preemption-free by committing each admitted request's exact block
    need against its (trial, shard) partition and deferring that arch's
    admission when it would not fit (overcommit headroom is a batcher knob,
    see serve/paging.py).

    ``hit_rate`` (paged only) is the expected fraction of prompt+generation
    tokens served from shared radix-cached blocks (serve/prefix_cache.py):
    a cached block is resident once no matter how many concurrent requests
    read it, so each row's expected *new*-block demand shrinks by the hit
    rate and the same pool backs proportionally more slots. Plan with the
    traffic's measured prefix redundancy; the runtime batcher still commits
    exact per-request (non-cached) needs, so an optimistic hit_rate degrades
    to deferred admission, never to preemption.

    ``overcommit`` (paged only) widens the planned grid past the pool's
    expected-demand capacity by the same factor the runtime batcher admits
    past it: above 1.0 the engine trades occasional retraction (preemptive
    swap-out/recompute of the youngest request) for higher steady-state
    occupancy on bursty traces. ``host_blocks`` sizes the per-partition
    host spill tier carried into the returned config — it extends prefix
    retention and absorbs retraction payloads (cheap host DRAM), but backs
    no compute, so it never widens the grid itself.
    """
    if not 0.0 <= hit_rate < 1.0:
        raise ValueError(f"hit_rate must be in [0, 1), got {hit_rate}")
    if overcommit < 1.0:
        raise ValueError(f"planning overcommit must be >= 1.0 (the batcher "
                         f"accepts < 1.0 as a runtime safety margin, but a "
                         f"grid planned below capacity is dead weight), "
                         f"got {overcommit}")
    if host_blocks < 0:
        raise ValueError(f"host_blocks must be >= 0, got {host_blocks}")
    if (overcommit > 1.0 or host_blocks > 0) and not paged:
        raise ValueError("overcommit > 1.0 and host_blocks require "
                         "paged=True (dense strips cannot be retracted or "
                         "spilled)")
    budget = (HBM_BYTES_PER_CHIP if hbm_bytes is None
              else hbm_bytes) * budget_fraction
    if mix is not None:
        if not mix or any(w < 0 for w, _ in mix) \
                or sum(w for w, _ in mix) <= 0:
            raise ValueError(f"mix must be non-empty (weight, expected_seq) "
                             f"pairs with positive total weight, got {mix}")
        k_trials = len(mix)
        w_total = sum(w for w, _ in mix)
        # per-row expected demand of trial k, scaled by its arrival share
        # (uniform weights -> demand_k = expected_k)
        demands = [min(max(int(e), 1), max_seq) * (w * k_trials / w_total)
                   for w, e in mix]
    else:
        k_trials = 1
        demands = [min(max(expected_seq or max_seq // 2, 1), max_seq)]
    s = base_eng.n_stages
    if s > 1:
        m_bubble = math.ceil((s - 1) * (1.0 - target_bubble)
                             / max(target_bubble * k_trials, 1e-9))
    else:
        m_bubble = 1
    if paged:
        eng = dataclasses.replace(base_eng, n_trials=k_trials,
                                  max_seq=max_seq, paged=True,
                                  block_size=block_size, n_blocks=0,
                                  n_microbatches=1)
        est = per_chip_bytes(cfg, dataclasses.replace(eng, n_trials=1),
                             max_seq, train=False)
        # act_bytes is the per-tick transient working set and does NOT scale
        # with K: the serve scan advances one slot per stage per tick, so K
        # only lengthens the scan (K·M+S−1 ticks), never widens a tick
        fixed = (est.params_bytes + est.opt_bytes) * k_trials + est.act_bytes
        token_b = kv_token_bytes_per_chip(cfg, eng)
        dp = 1 if eng.batch_replicated else eng.data_size * eng.pod_size
        # (ceil-div mirrors serve/paging.py::blocks_for; core/ stays below
        # serve/ in the layering so it is not imported here)
        per_row = -(-max_seq // block_size)
        # floor: every (trial, shard) partition must back a full max_seq
        # request, or the batcher would hard-reject in-spec traffic at
        # enqueue time. local_blocks is per chip PER TRIAL.
        local_blocks = max(
            int(budget - fixed) // (token_b * block_size * k_trials),
            per_row)
        # prefix sharing: hit tokens ride on blocks resident once per
        # partition, so only (1 - hit_rate) of each row's tokens demand
        # fresh blocks
        mean_demand = max(sum(demands) / k_trials * (1.0 - hit_rate), 1.0)
        # overcommit admits past the pool by the same factor at runtime
        # (retraction absorbs the tail), so the planned grid widens with it
        m_cap = int(local_blocks * block_size * overcommit
                    // (mean_demand * eng.microbatch))
        m = min(max_slots, max(1, m_cap))
        # blocks beyond the capped grid's worst case are dead weight (every
        # cell fully backed at max_seq) — return them to the budget
        local_blocks = min(local_blocks, max(eng.microbatch * m, 1) * per_row)
        return dataclasses.replace(eng, n_microbatches=m,
                                   n_blocks=local_blocks * dp,
                                   host_blocks=host_blocks)
    m = min(max(m_bubble, base_eng.n_microbatches, 1), max_slots)
    eng = dataclasses.replace(base_eng, n_trials=k_trials, n_microbatches=m,
                              max_seq=max_seq)

    def total(e):
        one = per_chip_bytes(cfg, dataclasses.replace(e, n_trials=1),
                             max_seq, train=False)
        return ((one.params_bytes + one.opt_bytes + one.cache_bytes)
                * k_trials + one.act_bytes)

    while total(eng) > budget and eng.n_microbatches > 1:
        eng = dataclasses.replace(eng, n_microbatches=eng.n_microbatches - 1)
    return eng


# ---------------------------------------------------------------------------
# Gang planning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GangPlan:
    """A set of same-architecture trials trained in one SPMD program."""

    arch: str
    trials: tuple  # TrialSpec...
    engine: EngineConfig

    @property
    def bubble_fraction(self) -> float:
        return self.engine.bubble_fraction


def plan_gangs(trials: Sequence[TrialSpec], base_eng: EngineConfig,
               arch_configs: dict, seq_len: int,
               target_bubble: float = 0.10,
               train: bool = True) -> list[GangPlan]:
    """Greedy gang former: group by architecture, split into capacity-bounded
    gangs, and size microbatch counts so each gang's bubble fraction meets
    ``target_bubble`` when memory allows.

    The paper's key scheduling claim (utilization → 1) is exactly the bubble
    fraction (S−1)/(K·M+S−1) → 0; this planner drives it below the target by
    raising K (more trials per gang) first — the Hydra move — and M second.
    """
    by_arch: dict[str, list[TrialSpec]] = {}
    for t in trials:
        by_arch.setdefault(t.arch, []).append(t)

    gangs = []
    for arch, ts in by_arch.items():
        cfg = arch_configs[arch]
        k_max = max_concurrent_trials(cfg, base_eng, seq_len, train)
        i = 0
        while i < len(ts):
            k = min(k_max, len(ts) - i)
            # choose M so bubble <= target: (S-1)/(K*M+S-1) <= target
            s = base_eng.n_stages
            m_needed = max(1, math.ceil(
                (s - 1) * (1 - target_bubble) / (target_bubble * k)))
            eng = dataclasses.replace(base_eng, n_trials=k,
                                      n_microbatches=m_needed)
            # shrink M if memory no longer fits
            while (per_chip_bytes(cfg, eng, seq_len, train).total * k
                   > HBM_BYTES_PER_CHIP * HBM_BUDGET_FRACTION
                   and eng.n_microbatches > 1):
                eng = dataclasses.replace(
                    eng, n_microbatches=eng.n_microbatches - 1)
            gangs.append(GangPlan(arch=arch, trials=tuple(ts[i:i + k]),
                                  engine=eng))
            i += k
    return gangs


# ---------------------------------------------------------------------------
# Failure / straggler policy (used by runtime/elastic.py + simulator)
# ---------------------------------------------------------------------------


def replan_after_failure(gangs: list[GangPlan], base_eng: EngineConfig,
                         arch_configs: dict, seq_len: int,
                         lost_data_rows: int) -> list[GangPlan]:
    """Shrink the data axis by the cordoned rows and re-form gangs.

    A failed chip cordons its entire data row (the stage axis is a hard
    dependency ring; the data axis is the elastic one). Trials keep their
    identity — training resumes from the last checkpoint with a smaller
    data axis, which changes throughput but not gradients (global batch is
    re-sharded, not re-sized).
    """
    new_data = base_eng.data_size - lost_data_rows
    if new_data < 1:
        raise RuntimeError("mesh lost all data rows; cannot re-plan")
    shrunk = dataclasses.replace(base_eng, data_size=new_data)
    trials = [t for g in gangs for t in g.trials]
    return plan_gangs(trials, shrunk, arch_configs, seq_len)
