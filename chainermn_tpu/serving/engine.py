"""Continuous-batching inference engine on the mesh stack.

One fixed-shape jitted forward serves every step: each of the ``B``
slots contributes up to ``S = chunk_tokens`` tokens — a prefill chunk, a
single decode token, or nothing (idle/finished slots write to the trash
page and their logits are ignored) — so prefill and decode FUSE into one
batched forward that never recompiles.  The step loop is:

1. rank 0 builds the admission plan (retire finished, pack waiting
   requests into free pages) and broadcasts it over the DCN control
   plane (:mod:`chainermn_tpu.runtime.control_plane`) so every
   controller applies the identical plan — lockstep by construction;
2. the fused forward writes the step's K/V into the paged cache, runs
   cache-offset-aware causal flash attention per layer, and greedily
   samples each slot's last valid position;
3. host state advances: sampled tokens append to their sequences,
   finished sequences retire next step.

With ``tp_size > 1`` the forward runs inside ``shard_map`` over a
``"tp"`` mesh axis: params are Megatron-sliced
(:func:`chainermn_tpu.serving.weights.shard_params_tp`), the KV cache is
sharded over its kv heads, and the blocks psum their row-parallel
outputs (:class:`chainermn_tpu.models.transformer.Block`), so the logits
— and therefore the greedy samples — are replicated across the axis.

With ``ep_size > 1`` (MoE models only) the mesh grows an ``"ep"`` axis
— ``(tp, ep)`` devices, axes ``("tp", "ep")`` — and every block's MoE
MLP dispatches its tokens over ``"ep"``: each device hosts
``moe_experts / ep_size`` experts and the two all-to-all exchanges ride
the step's one shard_map.  Tokens and gate math are replicated over the
axis, so the logits stay replicated (ep=2 decode is bit-identical to
ep=1) while expert FLOPs split ``ep`` ways.  ``moe_plan`` routes the
exchanges through the collective planner
(:func:`chainermn_tpu.planner.compiler.execute_alltoall`) so the
dispatch is a census-visible plan stage.

Wall-clock is only ever read on the host (latency bookkeeping); nothing
traced depends on time.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.serving import kv_cache as _kv
from chainermn_tpu.serving.scheduler import AdmissionScheduler


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Engine knobs (cache sizing is ``docs/serving.md``'s main topic)."""

    page_size: int = 16           # tokens per KV page
    num_pages: int = 64           # allocatable pages (excl. trash)
    max_seqs: int = 4             # batch slots B
    chunk_tokens: int = 8         # S: prefill chunk / step token budget
    max_pages_per_seq: int = 8    # page-table width (max ctx / page_size)
    eos_id: Optional[int] = None
    policy: str = "continuous"    # or "static" (benchmark baseline)
    tp_size: int = 1              # tensor-parallel ways
    ep_size: int = 1              # expert-parallel ways (MoE models)
    moe_plan: Any = None          # all-to-all Plan for the MoE exchanges
    cache_dtype: Any = jnp.float32
    keep_logits: bool = False     # stash last-position logits per step
    prefix_cache: bool = False    # copy-on-write prompt-prefix sharing
    spec_k: int = 0               # draft tokens per decode step (0 = off)


@dataclasses.dataclass
class Completion:
    """A finished request (rank 0 carries the timing fields)."""

    rid: int
    prompt_len: int
    tokens: List[int]
    arrival: float = 0.0
    token_times: List[float] = dataclasses.field(default_factory=list)

    @property
    def ttft(self) -> float:
        return self.token_times[0] - self.arrival if self.token_times \
            else float("nan")


@dataclasses.dataclass
class StepResult:
    step: int
    plan: dict
    emitted: list                  # [(rid, token, n_generated)]
    completed: List[Completion]
    ran_forward: bool
    last_logits: Optional[np.ndarray] = None   # [B, vocab] (keep_logits)
    n_new: Optional[np.ndarray] = None
    spec: Optional[dict] = None    # rows/proposed/accepted/out_tokens


class InferenceEngine:
    """``submit()`` on rank 0, then ``step()`` in lockstep on every rank
    (or :meth:`run_until_idle` on a single controller)."""

    def __init__(self, model, params, config: ServingConfig, *,
                 plane=None, draft_model=None, draft_params=None):
        from chainermn_tpu.observability import flight_recorder as _flight
        from chainermn_tpu.observability.registry import (enabled,
                                                          get_registry)
        from chainermn_tpu.runtime.control_plane import get_control_plane

        cfg = config
        self.cfg = cfg
        self.plane = plane if plane is not None else get_control_plane()
        self.model = model
        n_kv = model.n_kv_heads or model.n_heads
        head_dim = model.d_model // model.n_heads
        max_ctx = cfg.max_pages_per_seq * cfg.page_size
        if max_ctx + cfg.spec_k > model.max_len:
            raise ValueError(
                f"cache reach ({cfg.max_pages_per_seq} pages x "
                f"{cfg.page_size}) plus spec_k ({cfg.spec_k}) exceeds "
                f"the model's max_len ({model.max_len})")
        if cfg.spec_k:
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "spec_k > 0 requires a draft_model and draft_params")
            if cfg.chunk_tokens < cfg.spec_k + 1:
                raise ValueError(
                    f"spec_k ({cfg.spec_k}) needs chunk_tokens >= "
                    f"spec_k + 1 (the verify pass scores k+1 positions "
                    f"in the [B, S] step shape), got {cfg.chunk_tokens}")
            if draft_model.vocab != model.vocab:
                raise ValueError(
                    f"draft vocab ({draft_model.vocab}) != target vocab "
                    f"({model.vocab})")
            if max_ctx + cfg.spec_k > draft_model.max_len:
                raise ValueError(
                    f"cache reach plus spec_k exceeds the draft model's "
                    f"max_len ({draft_model.max_len})")
        self.scheduler = AdmissionScheduler(
            max_seqs=cfg.max_seqs, page_size=cfg.page_size,
            num_pages=cfg.num_pages,
            max_pages_per_seq=cfg.max_pages_per_seq,
            chunk_tokens=cfg.chunk_tokens, eos_id=cfg.eos_id,
            policy=cfg.policy, prefix_cache=cfg.prefix_cache)

        tp = cfg.tp_size
        ep = cfg.ep_size
        if ep > 1:
            if not model.moe_experts:
                raise ValueError(
                    f"ep_size ({ep}) > 1 requires an MoE model "
                    f"(moe_experts > 0)")
            if model.moe_experts % ep:
                raise ValueError(
                    f"ep_size ({ep}) must divide moe_experts "
                    f"({model.moe_experts})")
            if cfg.spec_k:
                raise ValueError(
                    "speculative decoding (spec_k > 0) is not supported "
                    "with ep_size > 1")
        # MoE models always take the mesh path — their expert dispatch
        # needs the "ep" axis bound even at ep_size=1 (a 1-wide axis)
        moe = bool(getattr(model, "moe_experts", 0))
        if tp > 1 or ep > 1 or moe:
            from chainermn_tpu.serving.weights import shard_params_tp

            if n_kv % tp:
                raise ValueError(
                    f"tp_size ({tp}) must divide n_kv_heads ({n_kv})")
            devs = jax.devices()
            if len(devs) < tp * ep:
                raise ValueError(
                    f"tp_size {tp} x ep_size {ep} exceeds the "
                    f"{len(devs)} visible devices")
            if ep > 1 or moe:
                self._mesh = jax.sharding.Mesh(
                    np.array(devs[:tp * ep]).reshape(tp, ep),
                    ("tp", "ep"))
                self._model_tp = model.clone(
                    tp_size=tp, tp_axis="tp" if tp > 1 else None,
                    moe_axis="ep", moe_plan=cfg.moe_plan)
            else:
                self._mesh = jax.sharding.Mesh(np.array(devs[:tp]),
                                               ("tp",))
                self._model_tp = model.clone(tp_size=tp, tp_axis="tp")
            # Re-place everything onto THIS engine's tp mesh: params may
            # arrive committed elsewhere (e.g. the run_spmd output of
            # broadcast_inference_params lives on the communicator's
            # full-device mesh), and jit refuses mixed device sets.
            tp_sharding = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec("tp"))
            sliced = shard_params_tp(
                params, tp, n_heads=model.n_heads, n_kv_heads=n_kv) \
                if tp > 1 else jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (1,) + x.shape),
                    params)
            self._params = jax.device_put(sliced, tp_sharding)
            cache = _kv.init_kv_cache(
                model.n_layers, cfg.num_pages, cfg.page_size,
                n_kv // tp, head_dim, cfg.cache_dtype)
            stack_tp = lambda x: jax.device_put(
                jnp.broadcast_to(x[None], (tp,) + x.shape), tp_sharding)
            self._ck, self._cv = stack_tp(cache.k), stack_tp(cache.v)
        else:
            self._mesh = None
            self._model_tp = model
            self._params = params
            cache = _kv.init_kv_cache(
                model.n_layers, cfg.num_pages, cfg.page_size,
                n_kv, head_dim, cfg.cache_dtype)
            self._ck, self._cv = cache.k, cache.v
        self._fwd = self._build_forward()

        self.draft_model = draft_model
        self._fwd_spec = None
        self._last_spec = None      # (step, accept decisions) of the last
        #                             spec forward — lockstep-verified via
        #                             the next step's plan envelope
        self._spec_pickups = 0
        if cfg.spec_k:
            dn_kv = draft_model.n_kv_heads or draft_model.n_heads
            dhead = draft_model.d_model // draft_model.n_heads
            if tp > 1:
                from chainermn_tpu.serving.weights import shard_params_tp
                if dn_kv % tp:
                    raise ValueError(
                        f"tp_size ({tp}) must divide the draft model's "
                        f"n_kv_heads ({dn_kv})")
                self._draft_tp = draft_model.clone(tp_size=tp,
                                                   tp_axis="tp")
                self._dparams = jax.device_put(shard_params_tp(
                    draft_params, tp, n_heads=draft_model.n_heads,
                    n_kv_heads=dn_kv), tp_sharding)
                dcache = _kv.init_kv_cache(
                    draft_model.n_layers, cfg.num_pages, cfg.page_size,
                    dn_kv // tp, dhead, cfg.cache_dtype)
                self._dck = stack_tp(dcache.k)
                self._dcv = stack_tp(dcache.v)
            else:
                self._draft_tp = draft_model
                self._dparams = draft_params
                dcache = _kv.init_kv_cache(
                    draft_model.n_layers, cfg.num_pages, cfg.page_size,
                    dn_kv, dhead, cfg.cache_dtype)
                self._dck, self._dcv = dcache.k, dcache.v
            self._fwd_spec = self._build_forward_spec()

        self._step_idx = 0
        self._arrivals: Dict[int, float] = {}
        self._token_times: Dict[int, List[float]] = {}
        self.completions: List[Completion] = []
        reg = get_registry() if enabled() else None
        self._m = None
        if reg is not None:
            self._m = {
                "steps": reg.counter("serving_steps",
                                     "engine steps run"),
                "gen": reg.counter("serving_generated_tokens",
                                   "tokens sampled and emitted"),
                "prefill": reg.counter("serving_prefill_tokens",
                                       "prompt tokens written to cache"),
                "admitted": reg.counter("serving_admitted",
                                        "requests admitted into slots"),
                "retired": reg.counter("serving_retired",
                                       "sequences retired"),
                "active": reg.gauge("serving_active_seqs",
                                    "occupied slots"),
                "queue": reg.gauge("serving_queue_depth",
                                   "waiting requests (rank 0)"),
                "pages": reg.gauge("serving_free_pages",
                                   "free KV pages"),
                # latency SLO family: streaming histograms (mergeable
                # fixed log-grid buckets) so the fleet-telemetry
                # aggregator can fold per-rank distributions into
                # exact fleet p50/p95/p99
                "step_s": reg.streaming_histogram(
                    "serving_step_seconds",
                    "wall time per engine step"),
                "ttft": reg.streaming_histogram(
                    "serving_ttft_seconds",
                    "arrival to first emitted token"),
                "tok_s": reg.streaming_histogram(
                    "serving_token_seconds",
                    "inter-token gap per emitted token"),
                # speculative-decoding family
                "spec_rows": reg.counter(
                    "serving_spec_rows",
                    "decode rows run through the draft+verify step"),
                "spec_proposed": reg.counter(
                    "serving_spec_proposed_tokens",
                    "draft tokens proposed (k per decode row)"),
                "spec_accepted": reg.counter(
                    "serving_spec_accepted_tokens",
                    "draft tokens accepted by the target verify pass"),
                "spec_out": reg.counter(
                    "serving_spec_out_tokens",
                    "tokens landed per verify pass (accepted + 1)"),
                # prefix-cache family (cumulative scheduler counters,
                # mirrored as gauges each step)
                "prefix_hits": reg.gauge(
                    "serving_prefix_hits", "admissions with a cache hit"),
                "prefix_hit_tokens": reg.gauge(
                    "serving_prefix_hit_tokens",
                    "prompt tokens served from shared pages"),
                "prefix_prompt_tokens": reg.gauge(
                    "serving_prefix_prompt_tokens",
                    "prompt tokens across all admissions"),
                "prefix_cached_pages": reg.gauge(
                    "serving_prefix_cached_pages",
                    "pages currently indexed by the prefix trie"),
                "prefix_evictions": reg.gauge(
                    "serving_prefix_evictions",
                    "trie pages evicted under page pressure"),
            }
        self._fr = _flight.get_flight_recorder()
        # last plan-table content hash this engine saw (online-tuner
        # hot-swaps ride the per-step plan broadcast — see step())
        self._plan_table_hash = None

    # -- online-tuner plan-table pickup --------------------------------------
    def _attach_plan_table(self, plan):
        """Rank-0 side: when the online tuner hot-swapped a plan table
        since this engine last broadcast one, piggyback the table on the
        step's plan envelope so every controller picks it up on the SAME
        serving step (the scheduler bcast is already the engine's
        lockstep decision channel)."""
        from chainermn_tpu.planner.online import (active_plan_table_meta,
                                                  get_active_plan_table)
        meta = active_plan_table_meta()
        if meta is not None and meta["table_hash"] != self._plan_table_hash:
            table = get_active_plan_table()
            plan = dict(plan, plan_table={
                "table_hash": meta["table_hash"],
                "swap_step": meta["swap_step"],
                "table": table.to_dict()})
        return plan

    def _pickup_plan_table(self, plan):
        """Every rank: strip a piggybacked plan table off the envelope,
        register it as this process's active table (sidecar pin +
        ``AutoCommunicator`` swaps read it), and mark the pickup with a
        flight event."""
        if not isinstance(plan, dict) or "plan_table" not in plan:
            return plan
        from chainermn_tpu.planner.autotune import PlanTable
        from chainermn_tpu.planner.online import set_active_plan_table
        entry = plan.pop("plan_table")
        if entry["table_hash"] != self._plan_table_hash:
            self._plan_table_hash = entry["table_hash"]
            if self.plane.rank != 0:
                set_active_plan_table(
                    PlanTable.from_dict(entry["table"]),
                    step=entry.get("swap_step"))
            if self._fr is not None:
                self._fr.record("plan_table_swap_pickup",
                                step=self._step_idx,
                                table_hash=entry["table_hash"],
                                swap_step=entry.get("swap_step"))
        return plan

    # -- spec-decode accept decisions on the plan envelope --------------------
    def _attach_spec(self, plan):
        """Rank-0 side: piggyback the previous step's accept/reject
        decisions on the plan broadcast.  Every rank computed the same
        decisions locally (argmax on replicated logits), so this is the
        lockstep PROOF channel, not the data channel — followers verify
        and fail loudly on divergence instead of silently forking."""
        if self._last_spec is not None:
            plan = dict(plan, spec={"step": self._last_spec[0],
                                    "decisions": self._last_spec[1]})
        return plan

    def _pickup_spec(self, plan):
        """Every rank: check rank 0's broadcast accept decisions against
        the ones this rank applied last step."""
        if not isinstance(plan, dict) or "spec" not in plan:
            return plan
        entry = plan.pop("spec")
        mine = self._last_spec
        if (mine is None or entry["step"] != mine[0]
                or entry["decisions"] != mine[1]):
            raise RuntimeError(
                f"lockstep desync: rank 0 broadcast spec-decode accept "
                f"decisions {entry} but this rank applied "
                f"{ {'step': None if mine is None else mine[0], 'decisions': None if mine is None else mine[1]} }")
        self._spec_pickups += 1
        return plan

    # -- forward -------------------------------------------------------------
    def _build_forward(self):
        model = self._model_tp
        n_layers = model.n_layers

        def forward(params, ck, cv, page_table, tokens, pos0, n_new):
            new_k: list = [None] * n_layers
            new_v: list = [None] * n_layers

            def attend(layer, q, k, v):
                lk = _kv.write_kv(ck[layer], page_table, pos0, n_new, k)
                lv = _kv.write_kv(cv[layer], page_table, pos0, n_new, v)
                new_k[layer], new_v[layer] = lk, lv
                return _kv.paged_attention(q, lk, lv, page_table, pos0)

            logits = model.apply(params, tokens, pos_offset=pos0,
                                 attend=attend)
            last = jnp.clip(n_new - 1, 0, tokens.shape[1] - 1)
            last_logits = jnp.take_along_axis(
                logits, last[:, None, None], axis=1)[:, 0]  # [B, vocab]
            sampled = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
            return sampled, last_logits, jnp.stack(new_k), jnp.stack(new_v)

        if self._mesh is None:
            return jax.jit(forward)

        from jax.sharding import PartitionSpec as P

        def body(params_st, ck_st, cv_st, page_table, tokens, pos0, n_new):
            params = jax.tree.map(lambda x: x[0], params_st)
            sampled, last_logits, nk, nv = forward(
                params, ck_st[0], cv_st[0], page_table, tokens, pos0,
                n_new)
            return sampled, last_logits, nk[None], nv[None]

        return jax.jit(jax.shard_map(
            body, mesh=self._mesh,
            in_specs=(P("tp"), P("tp"), P("tp"), P(), P(), P(), P()),
            out_specs=(P(), P(), P("tp"), P("tp")), check_vma=False))

    def _build_forward_spec(self):
        """Fused draft+verify step (one jitted program, fixed [B, S]).

        Decode rows: the draft model greedily proposes ``k`` tokens in
        ``k`` micro-steps (its KV rides the same page tables in its own
        cache arrays), then ONE target pass scores all ``k+1`` positions
        ``[t0, d1..dk]``; the longest matching prefix is accepted and
        position ``a`` contributes the correction/bonus token, so every
        verify pass lands ``a+1`` tokens.  Rollback is free by
        construction: rejected positions hold stale KV strictly above
        every live query position (causal-masked) and the next step's
        writes start at the rolled-back ``pos0``, overwriting them
        before anything can attend.  Prefill rows flow through both
        models untouched (the draft must prefill too — its cache has to
        cover the prompt before it can extend it).
        """
        tmodel = self._model_tp
        dmodel = self._draft_tp
        K = self.cfg.spec_k

        def run(model, params, ck, cv, page_table, tokens, pos0, n_new):
            nl = model.n_layers
            new_k: list = [None] * nl
            new_v: list = [None] * nl

            def attend(layer, q, k, v):
                lk = _kv.write_kv(ck[layer], page_table, pos0, n_new, k)
                lv = _kv.write_kv(cv[layer], page_table, pos0, n_new, v)
                new_k[layer], new_v[layer] = lk, lv
                return _kv.paged_attention(q, lk, lv, page_table, pos0)

            logits = model.apply(params, tokens, pos_offset=pos0,
                                 attend=attend)
            return logits, jnp.stack(new_k), jnp.stack(new_v)

        def forward_spec(params, dparams, ck, cv, dck, dcv, page_table,
                         tokens, pos0, n_new, is_decode, prev):
            b, s = tokens.shape
            dec = is_decode.astype(bool)
            # draft pass 1: prefill rows feed their chunk; decode rows
            # feed [prev, t0] at positions L-1, L -> d1.  Re-feeding the
            # second-to-last token heals the draft cache after a fully
            # accepted round (the bonus token's predecessor was never
            # drafted, so its draft KV is missing); in every other round
            # the rewrite is an identical-value no-op.
            d_tok1 = jnp.where(
                dec[:, None],
                jnp.zeros((b, s), jnp.int32)
                .at[:, 0].set(prev).at[:, 1].set(tokens[:, 0]),
                tokens)
            d_n1 = jnp.where(dec, 2, n_new)
            d_pos1 = jnp.where(dec, pos0 - 1, pos0)
            dlog, dck, dcv = run(dmodel, dparams, dck, dcv, page_table,
                                 d_tok1, d_pos1, d_n1)
            last1 = jnp.clip(d_n1 - 1, 0, s - 1)
            cur = jnp.argmax(jnp.take_along_axis(
                dlog, last1[:, None, None], axis=1)[:, 0],
                axis=-1).astype(jnp.int32)
            drafts = [cur]
            for i in range(1, K):   # micro-steps: d_i at position L+i
                step_tokens = jnp.zeros((b, s), jnp.int32
                                        ).at[:, 0].set(cur)
                dlog, dck, dcv = run(dmodel, dparams, dck, dcv,
                                     page_table, step_tokens, pos0 + i,
                                     jnp.where(dec, 1, 0))
                cur = jnp.argmax(dlog[:, 0], axis=-1).astype(jnp.int32)
                drafts.append(cur)
            d_mat = jnp.stack(drafts, axis=1)            # [B, K]
            # one target pass over [t0, d1..dk] (decode) / chunk (prefill)
            dec_tokens = jnp.concatenate(
                [tokens[:, :1], d_mat,
                 jnp.zeros((b, s - (K + 1)), jnp.int32)], axis=1)
            ver_tokens = jnp.where(dec[:, None], dec_tokens, tokens)
            t_n = jnp.where(dec, K + 1, n_new)
            tlog, ck, cv = run(tmodel, params, ck, cv, page_table,
                               ver_tokens, pos0, t_n)
            g = jnp.argmax(tlog[:, :K + 1, :], axis=-1).astype(jnp.int32)
            # greedy accept: longest leading prefix with d_i == g_{i-1}
            match = (d_mat == g[:, :K]).astype(jnp.int32)
            a = jnp.cumprod(match, axis=1).sum(axis=1)   # [B] accepted
            j_idx = jnp.arange(K + 1)[None, :]
            g_a = jnp.take_along_axis(g, a[:, None], axis=1)  # correction
            d_pad = jnp.concatenate(
                [d_mat, jnp.zeros((b, 1), jnp.int32)], axis=1)
            out_dec = jnp.where(j_idx < a[:, None], d_pad, g_a)
            # prefill rows: greedy token at the last valid position
            lastp = jnp.clip(n_new - 1, 0, s - 1)
            p_logits = jnp.take_along_axis(
                tlog, lastp[:, None, None], axis=1)[:, 0]    # [B, vocab]
            p_tok = jnp.argmax(p_logits, axis=-1).astype(jnp.int32)
            out_pre = jnp.concatenate(
                [p_tok[:, None], jnp.zeros((b, K), jnp.int32)], axis=1)
            out = jnp.where(dec[:, None], out_dec, out_pre)  # [B, K+1]
            n_out = jnp.where(dec, a + 1, 1)
            last_logits = jnp.where(dec[:, None], tlog[:, 0, :], p_logits)
            return out, n_out, last_logits, ck, cv, dck, dcv

        if self._mesh is None:
            return jax.jit(forward_spec)

        from jax.sharding import PartitionSpec as P

        def body(params_st, dparams_st, ck_st, cv_st, dck_st, dcv_st,
                 page_table, tokens, pos0, n_new, is_decode, prev):
            params = jax.tree.map(lambda x: x[0], params_st)
            dparams = jax.tree.map(lambda x: x[0], dparams_st)
            out, n_out, last_logits, ck, cv, dck, dcv = forward_spec(
                params, dparams, ck_st[0], cv_st[0], dck_st[0], dcv_st[0],
                page_table, tokens, pos0, n_new, is_decode, prev)
            return (out, n_out, last_logits, ck[None], cv[None],
                    dck[None], dcv[None])

        return jax.jit(jax.shard_map(
            body, mesh=self._mesh,
            in_specs=(P("tp"), P("tp"), P("tp"), P("tp"), P("tp"),
                      P("tp"), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), P(), P(), P("tp"), P("tp"), P("tp"), P("tp")),
            check_vma=False))

    # -- client side ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               arrival: Optional[float] = None) -> int:
        """Queue a request (rank 0).  ``arrival`` defaults to now."""
        arrival = time.perf_counter() if arrival is None else arrival
        rid = self.scheduler.submit(list(map(int, prompt)),
                                    max_new_tokens, arrival)
        self._arrivals[rid] = arrival
        return rid

    def idle(self) -> bool:
        return self.scheduler.idle()

    # -- the step loop -------------------------------------------------------
    def step(self) -> StepResult:
        t0 = time.perf_counter()
        sched = self.scheduler
        if self.plane.size > 1:
            plan = self._attach_spec(self._attach_plan_table(
                sched.build_plan())) if self.plane.rank == 0 else None
            btok = None
            if self._fr is not None:
                btok = self._fr.span_begin("object", "serving_plan_bcast",
                                           step=self._step_idx)
            plan = self.plane.bcast_obj(plan, root=0)
            if self._fr is not None:
                self._fr.span_end(btok)
        else:
            plan = self._attach_spec(self._attach_plan_table(
                sched.build_plan()))
        plan = self._pickup_spec(self._pickup_plan_table(plan))
        tok = None
        if self._fr is not None:
            tok = self._fr.span_begin(
                "serving", "serving_step", step=self._step_idx,
                admitted=len(plan["admit"]), retired=len(plan["retire"]))
        retired = sched.apply_plan(plan)
        completed = [self._finish(slot) for _, slot in retired]

        batch = sched.step_batch()
        n_new = batch["n_new"]
        ran = bool(n_new.sum())
        emitted: list = []
        last_logits = None
        spec_stats = None
        if ran:
            ftok = None
            if self._fr is not None:
                # the decode/prefill forward sub-span: fwd dispatch plus
                # the sampled-token sync — the device-bound slice of a
                # serving step the attribution lane separates from
                # scheduling/bcast time
                n_arr = np.asarray(n_new)
                ftok = self._fr.span_begin(
                    "serving", "serving_forward", step=self._step_idx,
                    n_new=int(n_arr.sum()),
                    decode_slots=int((n_arr == 1).sum()),
                    prefill_slots=int((n_arr > 1).sum()),
                    spec=bool(self._fwd_spec is not None))
            if self._fwd_spec is not None:
                dec = batch["decode"]
                out_d, n_out_d, logits_d, self._ck, self._cv, \
                    self._dck, self._dcv = self._fwd_spec(
                        self._params, self._dparams, self._ck, self._cv,
                        self._dck, self._dcv,
                        jnp.asarray(batch["page_table"]),
                        jnp.asarray(batch["tokens"]),
                        jnp.asarray(batch["pos0"]), jnp.asarray(n_new),
                        jnp.asarray(dec), jnp.asarray(batch["prev"]))
                out = np.asarray(out_d)       # device sync point
                n_out = np.asarray(n_out_d)
                if self.cfg.keep_logits:
                    last_logits = np.asarray(logits_d)
                if self._fr is not None:
                    self._fr.span_end(ftok)
                emitted = sched.note_sampled_spec(n_new, out, n_out)
                decisions = [
                    [int(i), int(n_out[i]),
                     [int(t) for t in out[i, :n_out[i]]]]
                    for i in range(len(n_out))
                    if dec[i] and n_new[i] > 0]
                self._last_spec = [self._step_idx, decisions]
                rows = len(decisions)
                spec_stats = {
                    "rows": rows,
                    "proposed": rows * self.cfg.spec_k,
                    "accepted": sum(d[1] - 1 for d in decisions),
                    "out_tokens": sum(d[1] for d in decisions),
                }
            else:
                sampled_d, logits_d, self._ck, self._cv = self._fwd(
                    self._params, self._ck, self._cv,
                    jnp.asarray(batch["page_table"]),
                    jnp.asarray(batch["tokens"]),
                    jnp.asarray(batch["pos0"]),
                    jnp.asarray(n_new))
                sampled = np.asarray(sampled_d)   # device sync point
                if self.cfg.keep_logits:
                    last_logits = np.asarray(logits_d)
                if self._fr is not None:
                    self._fr.span_end(ftok)
                emitted = sched.note_sampled(n_new, sampled)
            now = time.perf_counter()
            for rid, _tok, _n in emitted:
                times = self._token_times.setdefault(rid, [])
                if self._m is not None:
                    if times:
                        self._m["tok_s"].observe(now - times[-1])
                    else:
                        arrival = self._arrivals.get(rid)
                        if arrival is not None:
                            self._m["ttft"].observe(now - arrival)
                times.append(now)

        if self._m is not None:
            self._m["steps"].inc()
            self._m["gen"].inc(len(emitted))
            if spec_stats is None:
                self._m["prefill"].inc(int(n_new.sum()) - len(emitted))
            else:
                dec_arr = batch["decode"]
                self._m["prefill"].inc(
                    int(n_new[dec_arr == 0].sum()))
                self._m["spec_rows"].inc(spec_stats["rows"])
                self._m["spec_proposed"].inc(spec_stats["proposed"])
                self._m["spec_accepted"].inc(spec_stats["accepted"])
                self._m["spec_out"].inc(spec_stats["out_tokens"])
            self._m["admitted"].inc(len(plan["admit"]))
            self._m["retired"].inc(len(plan["retire"]))
            self._m["active"].set(sched.active_count)
            self._m["queue"].set(sched.queue_depth)
            self._m["pages"].set(sched.allocator.num_free)
            if sched.prefix is not None:
                ps = sched.prefix_stats()
                self._m["prefix_hits"].set(ps["hits"])
                self._m["prefix_hit_tokens"].set(ps["hit_tokens"])
                self._m["prefix_prompt_tokens"].set(ps["prompt_tokens"])
                self._m["prefix_cached_pages"].set(ps["cached_pages"])
                self._m["prefix_evictions"].set(ps["evictions"])
            self._m["step_s"].observe(time.perf_counter() - t0)
        if self._fr is not None:
            self._fr.span_end(
                tok, emitted=len(emitted), ran_forward=ran,
                spec_accepted=0 if spec_stats is None
                else spec_stats["accepted"])
        res = StepResult(step=self._step_idx, plan=plan, emitted=emitted,
                         completed=completed, ran_forward=ran,
                         last_logits=last_logits, n_new=n_new,
                         spec=spec_stats)
        self._step_idx += 1
        return res

    def _finish(self, slot) -> Completion:
        comp = Completion(
            rid=slot.rid, prompt_len=len(slot.prompt),
            tokens=list(slot.generated),
            arrival=self._arrivals.get(slot.rid, 0.0),
            token_times=self._token_times.pop(slot.rid, []))
        self.completions.append(comp)
        return comp

    def run_until_idle(self, max_steps: int = 10_000) -> List[Completion]:
        """Step until every submitted request has retired (single
        controller convenience; multi-controller worlds drive ``step()``
        in lockstep themselves)."""
        start = len(self.completions)
        for _ in range(max_steps):
            if self.idle():
                break
            self.step()
        else:
            raise RuntimeError(
                f"engine still busy after {max_steps} steps "
                f"(active={self.scheduler.active_count}, "
                f"queued={self.scheduler.queue_depth})")
        return self.completions[start:]


__all__ = ["Completion", "InferenceEngine", "ServingConfig", "StepResult"]
