"""`build_round(experiment)`: ONE round body, two lowerings.

Lowers an :class:`~repro.engine.Experiment` to a jit-able round function —
Algorithm 1's (local SGD steps → neighbour exchange → aggregation) as ONE
XLA program per round.  Every strategy × transport × dynamics combination
shares a single round body, written once against the transport layer's
:class:`~repro.comm.PodContext` (a row-slice + all-gather pair), and the
two backends differ ONLY in the context they bind:

  * ``vmap``      — the dense context (identity slice, identity gather):
    every per-node quantity vmapped over the full node axis — the small-N
    oracle;
  * ``shard_map`` — explicit shard_map over the "pod" mesh axis: each pod
    owns N/n_pods nodes' params, optimizer state, data shards and
    sender-private transport rows; the context's gather is a tiled
    `all_gather` over the pod ring carrying the transport's ENCODED payload
    by default (`Experiment(wire=...)` selects the decoded-rows oracle
    wire), and receiver-facing transport caches are replicated so the
    per-edge reverse-slot gather and the CFA-GE neighbour walk read them
    without further collectives.  Everything per-node — training, trigger,
    codec, aggregation, gradient exchange — runs with the SAME per-row ops
    as the dense context, so the two backends agree bit-for-bit (pinned in
    tests/test_engine.py and tests/test_exchange_unified.py on the
    4-device CPU mesh, across the full capability roster).

The round function's calling convention is ONE generic shape over the
four optional scan-carried subsystem states — the transport's comm state,
the `repro.dynamics` process state, the `repro.timing` event clock, and
the `repro.obs` telemetry accumulators — each present iff the experiment
carries the subsystem:

  (params, opt, *states, round_idx, rng)
    -> (params, opt, *states, rng, loss, *extras)

with `states` the present members of (comm_state, dyn_state, time_state,
obs_state) in that order, and `extras` the present accounting groups, in
the same order: (sent_edges, trig_frac) with a transport, (live_edges,)
with dynamics, (sim_time, arrived_edges) with timing, and (obs_snapshot,)
— a dict of per-round channel values — with telemetry.  The no-subsystem
case degenerates to the legacy (params, opt, round_idx, rng) -> (params,
opt, rng, loss).

With dynamics, the round starts by realizing this round's graph (one pure
state transition -> a GraphEvent): a dead node runs zero local steps and
its params/opt state freeze bit-exactly, the delivery mask is intersected
with the live-edge mask, transports only fire (and only account bytes) on
live edges, a node that rejoins after churn has its per-link transport
state reset before the exchange, and server-style aggregation intersects
its data-size weights with the live mask (an offline client's frozen
params carry zero weight).  `trig_frac` is the fired fraction of LIVE
directed edges; `live_edges` their count.  An OBSERVING process
(`EnergyChurn`) additionally receives the event clock's previous-round
realized per-node compute cost as its transition observation.

With timing, the round is priced in simulated seconds.  Under
`Schedule(deadline=d)` each round is a deadline TICK: node i's local-step
budget is capped at `floor(d / dt_i)` (stragglers train fewer steps), and
a payload on edge (j -> i) ARRIVES iff `t_cost_j + transfer_ji <= d`
(send time = the sender's realized compute; transfer = latency +
payload_bytes / bandwidth from the bound `repro.timing` tables).  The
arrival mask is intersected with the link/live masks in THIS one round
body — a late payload is indistinguishable from a failed link: the sender
burns its bytes, per-edge state freezes, and the silence path (stale
cache / drop) covers the receiver.  Without a deadline the schedule stays
synchronous — budgets are uncapped, everything arrives, and the tick is
the round's realized makespan (slowest node + slowest live transfer) — so
the degenerate model is bit-identical to timing=None by construction (no
extra rng is ever consumed: all time tables are bound numpy constants).

Method behaviour enters exclusively through the experiment's strategy
:class:`~repro.engine.Capabilities` record (kind / grad_exchange) and the
strategy's exchange/aggregate hooks — there is no method branching here
beyond the declared capabilities, and every capability lowers to every
backend.

Randomness discipline (the bit-exactness mechanism): every rng consumption
— per-step dropout keys, hetero step budgets, participation masks, codec
keys, gradient-exchange minibatch keys, and the dynamics process's edge
coins — is computed from the REPLICATED rng stream over the full node axis
and then row-sliced per block, so the shard_map lowering sees exactly the
values the vmap lowering sees.  Only data movement (the gather) differs,
and the transport's two wires carry bit-identical information by
construction (decode is deterministic).  A process that needs no rng
(StaticGraph, PeriodicRewiring) consumes none, which is what makes
`dynamics=StaticGraph()` bit-identical to `dynamics=None`.

Byte accounting is exact and replicated: the fired-edge gates come back
full-axis from the exchange, so `sent_edges` is the same full-array sum on
every pod (small integers, exact in f32) and the `payload_bytes ×
sent_edges` multiply happens in Python where it survives past f32's 2^24.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.comm import (DENSE_CTX, EdgeGossipTransport, PodContext,
                        SparseEdgeGossipTransport)
from repro.comm.trigger import edge_delivery
from repro.dist.sharding import NODE_AXIS
from repro.engine.neighborhood import DenseNeighborhood, SparseNeighborhood
from repro.obs.spans import AGGREGATE, EXCHANGE, TRAIN
from repro.timing import TimingState
from repro.utils.pytree import tree_flatten_stacked

BACKENDS = ("vmap", "shard_map")


def _and_masks(*ms):
    """Product of the non-None {0,1} float masks (None = all-ones = skip);
    None if every factor is absent.  Exact {0,1} products, so composition
    order cannot affect bits."""
    ms = [m for m in ms if m is not None]
    if not ms:
        return None
    out = ms[0]
    for m in ms[1:]:
        out = out * m
    return out


def build_round(exp):
    """Lower `exp` to its jit-able round function (see module docstring)."""
    if exp.backend == "vmap":
        return _build_vmap_round(exp)
    if exp.backend == "shard_map":
        return _build_shardmap_round(exp)
    raise ValueError(
        f"unknown backend {exp.backend!r}; available: {BACKENDS}")


# ------------------------------------------------------------ shared pieces

def _identity_rows(a):
    return a


def _freeze_dead(new_params, old_params, alive):
    """Per-node select: rows with alive == 0 keep their old value bit-exactly
    (gossip masks already guarantee it for aggregation; this also covers
    server-style strategies that would overwrite an offline device)."""
    def sel(nw, od):
        a = alive.reshape(alive.shape + (1,) * (nw.ndim - 1)) > 0
        return jnp.where(a, nw, od)

    return jax.tree.map(sel, new_params, old_params)


def _make_realize(exp):
    """The dynamics prelude: consume (at most) one rng split and run the
    process transition, yielding this round's GraphEvent.  An observing
    process additionally receives `obs` — the event clock's previous-round
    realized per-node compute cost (zeros at round 0)."""
    bound = exp.bound_dyn
    step, needs_rng, observes = bound.step, bound.needs_rng, bound.observes

    def realize(dyn_state, round_idx, rng, obs=None):
        if needs_rng:
            rng, dk = jax.random.split(rng)
        else:
            dk = None
        if observes:
            dyn_state, ev = step(dyn_state, round_idx, dk, obs)
        else:
            dyn_state, ev = step(dyn_state, round_idx, dk)
        return dyn_state, ev, rng

    return realize


def _make_local_training(exp, *, x, y, counts, rows, loss_reduce):
    """B local SGD(momentum) minibatch steps (Alg. 1 l.4-9) for the block of
    nodes whose data is (x, y, counts); `rows` slices globally-computed
    [N, ...] randomness to the block (identity on the vmap backend).
    `alive` ([N], optional) zeroes the step budget of churned-out devices —
    an offline node trains nothing and its params/opt state freeze.
    `cap` ([N] int32, optional) is the event clock's deadline cap
    (`floor(deadline / dt_i)`): a straggler trains only the steps that fit
    in the tick.  Returns the FULL-axis realized budgets alongside, so the
    clock can price each node's round at `budget_i * dt_i` seconds."""
    cfg = exp.train
    n = exp.n
    batcher = exp.batcher

    def take_batch(xx, yy, c, step):
        return batcher.take(xx, yy, c, step)

    v_take = jax.vmap(take_batch, in_axes=(0, 0, 0, None))
    v_step = jax.vmap(exp._train_step, in_axes=(0, 0, 0, 0, None, 0))

    def local_training(params, opt, round_idx, rng, alive=None, cap=None):
        # Heterogeneous E (Alg. 1): per-node step budget for this round;
        # nodes past their budget keep their params (masked update).
        # Budgets are computed FULL-axis (replicated rng, then capped and
        # alive-masked) and row-sliced, so every pod prices every node.
        if cfg.hetero_steps_min > 0:
            rng, sub = jax.random.split(rng)
            budgets_full = jax.random.randint(
                sub, (n,), cfg.hetero_steps_min, cfg.steps_per_round + 1)
        else:
            budgets_full = jnp.full((n,), cfg.steps_per_round, jnp.int32)
        if cap is not None:
            budgets_full = jnp.minimum(budgets_full, cap)
        if alive is not None:
            budgets_full = budgets_full * alive.astype(budgets_full.dtype)
        budgets = rows(budgets_full)

        def body(carry, b):
            params, opt, rng = carry
            step = round_idx * cfg.steps_per_round + b
            xb, yb = v_take(x, y, counts, step)
            rng, sub = jax.random.split(rng)
            drop_keys = rows(jax.random.split(sub, n))
            new_params, new_opt, loss = v_step(params, opt, xb, yb, step,
                                               drop_keys)
            active = (b < budgets).astype(jnp.float32)

            def mix(new, old):
                a = active.reshape(active.shape + (1,) * (new.ndim - 1))
                return (a * new.astype(jnp.float32)
                        + (1 - a) * old.astype(jnp.float32)).astype(old.dtype)

            params = jax.tree.map(mix, new_params, params)
            opt = jax.tree.map(mix, new_opt, opt)
            return (params, opt, rng), jnp.mean(loss)

        (params, opt, rng), losses = jax.lax.scan(
            body, (params, opt, rng), jnp.arange(cfg.steps_per_round))
        return params, opt, rng, loss_reduce(jnp.mean(losses)), budgets_full

    return local_training


def _make_delivery_mask(exp):
    """Exogenous per-edge Bernoulli link failures (the paper's
    no-synchronization model), drawn over the FULL [N, max_deg] layout
    (consumers row-slice at the use site, so every backend sees the same
    draws)."""
    cfg = exp.train
    nbr_valid = exp.nbr_valid

    def delivery_mask(rng):
        if cfg.participation >= 1.0:
            return nbr_valid
        u = jax.random.uniform(rng, nbr_valid.shape)
        return nbr_valid * (u < cfg.participation).astype(jnp.float32)

    return delivery_mask


def _make_gradient_exchange(exp):
    """CFA-GE second phase: neighbours evaluate our aggregated model on
    their data; we descend along the p_ij-weighted mean of their gradients.
    Runs per block row: `rows` slices the neighbour table and the
    replicated minibatch keys; the neighbour DATA is read out of the full
    (replicated) stride-ordered shards, which is what lets the walk cross
    pods without a collective.  Slot d of round r reads its senders'
    minibatch of step `r*max_deg + d` (`Batcher.take_nodes`)."""
    cfg = exp.train
    batcher = exp.batcher
    counts = exp.counts
    nbr_idx, nbr_weight = exp.nbr_idx, exp.nbr_weight
    x_shards, y_shards = exp.x_shards, exp.y_shards
    n = exp.n
    max_deg = int(nbr_idx.shape[1])
    v_grad = jax.vmap(exp._grad_fn, in_axes=(0, 0, 0, 0))

    def gradient_exchange(rows, params, mask, round_idx, rng):
        nbr_idx_r = rows(nbr_idx)
        nbr_w_r = rows(nbr_weight)
        r = int(nbr_idx_r.shape[0])

        def body(carry, d):
            acc, tot = carry
            j = nbr_idx_r[:, d]  # [r] neighbour ids in slot d
            xj, yj = batcher.take_nodes(x_shards, y_shards, counts, j,
                                        round_idx * max_deg + d)
            keys = rows(jax.random.split(jax.random.fold_in(rng, d), n))
            g = v_grad(params, xj, yj, keys)  # grad of F_j at w_i
            w_d = nbr_w_r[:, d] * mask[:, d]

            def add(a, gi):
                wb = w_d.reshape((r,) + (1,) * (gi.ndim - 1))
                return a + wb * gi.astype(jnp.float32)

            return (jax.tree.map(add, acc, g), tot + w_d), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        # totals ride the same scan as the gradient accumulator (not a
        # separate jnp.sum), so a walk truncated to any slot width that
        # covers every real neighbour — the sparse layout's power-of-two
        # bucket widths — accumulates bit-identical (acc, tot) pairs: the
        # trailing slots add exact +0 weights to a carry that starts at +0.
        (acc, tot), _ = jax.lax.scan(
            body, (zeros, jnp.zeros((r,), jnp.float32)), jnp.arange(max_deg))
        safe = jnp.maximum(tot, 1e-9)
        lr_ge = cfg.ge_lr if cfg.ge_lr is not None else cfg.lr

        def apply(p, a):
            wb = (1.0 / safe).reshape((r,) + (1,) * (a.ndim - 1))
            gate = (tot > 0).astype(jnp.float32).reshape((r,) + (1,) * (a.ndim - 1))
            return (p.astype(jnp.float32) - lr_ge * gate * wb * a).astype(p.dtype)

        return jax.tree.map(apply, params, acc)

    return gradient_exchange


def _make_sparse_gradient_exchange(exp):
    """CFA-GE second phase on the sparse layout: the SAME slot walk as
    `_make_gradient_exchange`, run over each width bucket's ragged slot
    tables instead of the `[N, max_deg]` panel.

    Bucket slot k of receiver i IS dense slot k — both enumerate i's CSR
    in-edges sender-ascending — so the minibatch base is computed with the
    GLOBAL dense max_degree and the per-slot keys fold the same k: every
    real slot consumes bit-identical neighbour data, dropout keys and
    composed weights.  Trailing zero-weight slots (a bucket's power-of-two
    width vs max_degree, in either direction) are neutral because both the
    gradient accumulator and the totals ride the scan carry from +0, and
    their padding sources (node 0's data, zero params on dummy rows) are
    finite.  Dummy bucket rows land on the [R+1] trash row and are sliced
    away, mirroring the SparseNeighborhood scatter."""
    cfg = exp.train
    batcher = exp.batcher
    counts = exp.counts
    x_shards, y_shards = exp.x_shards, exp.y_shards
    n = exp.n
    plan = exp.sparse_plan
    max_deg = int(exp.topo.max_degree)
    per_pod = plan.per_pod
    v_grad = jax.vmap(exp._grad_fn, in_axes=(0, 0, 0, 0))

    def take(a, pod):
        return jax.lax.dynamic_index_in_dim(a, pod, axis=0, keepdims=False)

    def pad_row(p):
        return jnp.concatenate([p, jnp.zeros((1,) + p.shape[1:], p.dtype)])

    def gradient_exchange(ctx, params, link_u, live_e, round_idx, rng):
        pod = ctx.pod if ctx.pod is not None else jnp.int32(0)
        lr_ge = cfg.ge_lr if cfg.ge_lr is not None else cfg.lr
        out = params
        for wd in plan.widths:
            bk = plan.buckets[wd]
            rows_local = take(bk.rows_local, pod)   # [B]
            src = take(bk.src, pod)                 # [B, wd]
            wgt = take(bk.wgt, pod)                 # [B, wd]
            epos = take(bk.epos, pod)               # [B, wd]
            b = int(rows_local.shape[0])
            m = jnp.ones_like(wgt)
            if cfg.participation < 1.0:
                m = m * (link_u[epos] < cfg.participation).astype(jnp.float32)
            if live_e is not None:
                m = m * live_e[epos]
            w_slot = wgt * m                        # [B, wd]
            p_b = jax.tree.map(lambda p: pad_row(p)[rows_local], params)
            gid = jnp.clip(pod * per_pod + rows_local, 0, n - 1)

            def body(carry, k):
                acc, tot = carry
                j = src[:, k]  # [b] sender ids in slot k
                xj, yj = batcher.take_nodes(x_shards, y_shards, counts, j,
                                            round_idx * max_deg + k)
                keys = jax.random.split(jax.random.fold_in(rng, k), n)[gid]
                g = v_grad(p_b, xj, yj, keys)  # grad of F_j at w_i
                w_k = w_slot[:, k]

                def add(a, gi):
                    wb = w_k.reshape((b,) + (1,) * (gi.ndim - 1))
                    return a + wb * gi.astype(jnp.float32)

                return (jax.tree.map(add, acc, g), tot + w_k), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), p_b)
            (acc, tot), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((b,), jnp.float32)), jnp.arange(wd))
            safe = jnp.maximum(tot, 1e-9)

            def apply(p, a):
                wb = (1.0 / safe).reshape((b,) + (1,) * (a.ndim - 1))
                gate = (tot > 0).astype(jnp.float32).reshape(
                    (b,) + (1,) * (a.ndim - 1))
                return (p.astype(jnp.float32)
                        - lr_ge * gate * wb * a).astype(p.dtype)

            new_b = jax.tree.map(apply, p_b, acc)
            out = jax.tree.map(
                lambda o, nb: pad_row(o).at[rows_local].set(nb)[:o.shape[0]],
                out, new_b)
        return out

    return gradient_exchange


# ----------------------------------------------------------- the round body

def _make_round_body(exp, *, loss_reduce):
    """The ONE round body, written against a PodContext.

    Returns ``body(ctx, params, opt, comm_state, dyn_state, time_state,
    obs_state, round_idx, rng, x, y)`` -> the full 14-slot tuple
    ``(params, opt, comm_state, dyn_state, time_state, obs_state, rng,
    loss, sent_edges, trig_frac, live_edges, sim_time, arrived_edges,
    obs_snapshot)`` with ``None`` in the slots the experiment does not
    carry (the backend wrappers squeeze those out to the documented
    calling conventions).  All branching below is on STATIC configuration
    — capabilities, transport type, dynamics/timing/telemetry presence —
    so each experiment traces exactly one path.
    """
    cfg, strategy, agg_state = exp.train, exp.strategy, exp.agg_state
    caps = strategy.capabilities
    transport = exp.transport
    per_edge = isinstance(transport,
                          (EdgeGossipTransport, SparseEdgeGossipTransport))
    wire = exp.wire
    nbr_idx, nbr_valid, nbr_weight = exp.nbr_idx, exp.nbr_valid, exp.nbr_weight
    counts = exp.counts
    n = exp.n
    has_dyn = exp.bound_dyn is not None
    realize = _make_realize(exp) if has_dyn else None
    dyn_observes = has_dyn and exp.bound_dyn.observes
    has_time = exp.bound_timing is not None
    has_obs = exp.bound_obs is not None
    tele = exp.bound_obs
    bt = exp.bound_timing
    deadline = exp.deadline if has_time else None
    step_time = bt.step_time if has_time else None
    transfer_e = bt.transfer_e if has_time else None
    transfer_panel = bt.transfer_panel if has_time else None
    sparse = exp.layout == "sparse"
    plan = exp.sparse_plan if sparse else None
    # Does this round exchange payloads over the graph?  Controls whether
    # the synchronous-mode clock tick includes the slowest live link's
    # landing time on top of the compute makespan.
    exchanges = (exp.transport is not None) or caps.kind == "gossip"
    # Gossip aggregation lowers to the strategy's flat form whenever one is
    # declared: one weighted neighbour reduce over a Neighborhood view, the
    # SAME code on both layouts (the dense view is the small-N oracle for
    # the sparse one, so the dense lowering must go through it too).  The
    # per-edge transport also lowers to it — its per-link caches cannot be
    # a single [N, D] table, so the Neighborhood is built over the
    # transport's pre-gathered panel instead (same kernel, same bits; this
    # is what keeps per-edge fp32/thr0 bit-exact vs the per-node round).
    # The padded-gather form remains only for strategies without a flat
    # form.
    use_flat = (caps.kind == "gossip"
                and strategy.flat_aggregate is not None)
    if sparse:
        degrees = plan.degrees
        total_edges = jnp.float32(plan.num_directed)
        delivery_mask = None
        edge_src = jnp.asarray(exp.topo.edge_src.astype(np.int32))
        edge_dst = jnp.asarray(exp.topo.edge_dst.astype(np.int32))
    else:
        delivery_mask = _make_delivery_mask(exp)
        degrees = jnp.sum(nbr_valid, axis=1)
        total_edges = jnp.sum(degrees)  # directed edge count
    if caps.grad_exchange:
        gradient_exchange = (_make_sparse_gradient_exchange(exp) if sparse
                             else _make_gradient_exchange(exp))

    @jax.named_scope(AGGREGATE)
    def aggregate(rows, params, gathered, mask):
        state = (jax.tree.map(rows, agg_state) if caps.kind == "gossip"
                 else agg_state)
        return strategy.aggregate(exp, state, params, gathered, mask)

    def body(ctx, params, opt, comm_state, dyn_state, time_state, obs_state,
             round_idx, rng, x, y):
        rows = ctx.rows
        local_training = _make_local_training(
            exp, x=x, y=y, counts=rows(counts), rows=rows,
            loss_reduce=loss_reduce)

        # -- dynamics prelude: realize this round's graph ------------------
        if has_dyn:
            obs = (time_state.last_cost
                   if has_time and dyn_observes else None)
            dyn_state, ev, rng = realize(dyn_state, round_idx, rng, obs)
            alive = ev.alive
        else:
            ev, alive = None, None

        # -- event-clock prelude: per-node step times + deadline cap -------
        # A deadline tick caps node i at floor(deadline / dt_i) local steps
        # (a straggler trains fewer); without a deadline (synchronous mode)
        # the budgets are untouched and the tick stretches to the realized
        # makespan below.  Timing consumes NO rng: dt comes from the bound
        # model's numpy draws keyed at bind time.
        if has_time:
            dt = step_time(round_idx)
            if deadline is not None:
                cap = jnp.minimum(
                    jnp.floor(jnp.float32(deadline) / dt),
                    jnp.float32(cfg.steps_per_round)).astype(jnp.int32)
            else:
                cap = None
        else:
            dt = cap = None

        # -- Alg. 1 l.4-9: local SGD (dead nodes run zero steps) -----------
        with jax.named_scope(TRAIN):
            params, opt, rng, train_loss, budgets_full = local_training(
                params, opt, round_idx, rng, alive=alive, cap=cap)
        # realized per-node compute cost this round (0 for dead nodes)
        t_cost = (budgets_full.astype(jnp.float32) * dt if has_time
                  else None)

        # -- exogenous link failures ∩ the live graph ----------------------
        # The split happens unconditionally on both layouts so the rng
        # stream stays aligned; the DRAWS differ by layout (dense draws the
        # [N, max_deg] panel, sparse one uniform per directed edge), which
        # is why oracle equivalence is stated at participation == 1.0 —
        # there, neither layout draws at all.
        rng, sub = jax.random.split(rng)
        # Arrival under a deadline tick: edge (j -> i)'s payload lands at
        # t_cost_j + latency_ji + bytes/bandwidth_ji and is delivered iff it
        # lands by the deadline.  A late payload is EXACTLY a failed link —
        # same freeze/stale/drop silence path, sender's bytes still burned.
        if sparse:
            link_full = arr_full = None
            link_u = (jax.random.uniform(sub, (plan.num_directed,))
                      if cfg.participation < 1.0 else None)
            arr_e = ((t_cost[edge_src] + transfer_e
                      <= jnp.float32(deadline)).astype(jnp.float32)
                     if deadline is not None else None)
        else:
            link_u = arr_e = None
            link_full = delivery_mask(sub)
            if has_dyn:
                link_full = link_full * ev.live
            if deadline is not None:
                arr_full = (t_cost[nbr_idx] + transfer_panel
                            <= jnp.float32(deadline)).astype(
                                jnp.float32) * nbr_valid
                link_full = link_full * arr_full
            else:
                arr_full = None
        old_params = params

        @jax.named_scope(AGGREGATE)
        def flat_gossip(params, gate_vec, table=None, edge_mask=None,
                        mask_full=None):
            """The flat-form gossip update: flatten the block's models,
            build the layout's Neighborhood over the full [N, D] table
            (flattened from `table`, the transport's decoded models, or
            gathered here without one), and run the strategy's flat
            aggregate.  `gate_vec` [N] {0,1} is the senders' broadcast
            gate; `edge_mask` [E] {0,1} is the sparse layout's per-edge
            factor (liveness ∩ arrival ∩ delivery history); `mask_full`
            [N, max_deg] {0,1} is the dense layout's fully-composed
            counterpart — when given it REPLACES the default gate·link
            composition (the per-node transport computes its silence
            semantics there)."""
            local_mat, unflatten = tree_flatten_stacked(params)
            if table is not None:
                table_mat = tree_flatten_stacked(table)[0]
            else:
                with jax.named_scope(EXCHANGE):
                    table_mat = ctx.gather(local_mat)
            if sparse:
                pod = ctx.pod if ctx.pod is not None else jnp.int32(0)
                nb = SparseNeighborhood(plan, pod, table_mat, local_mat,
                                        unflatten, gate_vec, link_u,
                                        cfg.participation,
                                        edge_mask=edge_mask)
            else:
                if mask_full is not None:
                    w = rows(nbr_weight) * rows(mask_full)
                else:
                    w = rows(nbr_weight) * edge_delivery(
                        gate_vec, rows(link_full), rows(nbr_idx))
                nb = DenseNeighborhood(table_mat, rows(nbr_idx), w,
                                       local_mat, unflatten)
            state = jax.tree.map(rows, agg_state)
            return strategy.flat_aggregate(exp, state, nb)

        # -- the exchange + aggregation, by declared capability ------------
        # With telemetry, each transport branch also captures its fired /
        # delivered edge masks in the RECEIVER orientation (the dense
        # [N, max_deg] panel or the flat [E] bank — the same full-axis
        # replicated quantities the byte accounting sums, so the channel
        # accumulators agree with `sent_edges` exactly).
        sent_edges = trig = new_comm = None
        obs_fired = obs_deliv = None
        if transport is None:
            if caps.kind == "server":
                # server-style: global average over the full stack, with
                # data-size weights intersected with liveness — an offline
                # client's frozen params carry zero weight (the all-ones
                # mask without dynamics is an exact no-op).
                with jax.named_scope(EXCHANGE):
                    full = jax.tree.map(ctx.gather, params)
                params = aggregate(rows, params, full, alive)
            elif caps.kind == "gossip":
                if use_flat:
                    params = flat_gossip(
                        params, jnp.ones((n,), jnp.float32),
                        edge_mask=_and_masks(
                            ev.live if sparse and has_dyn else None, arr_e))
                else:
                    with jax.named_scope(EXCHANGE):
                        full = jax.tree.map(ctx.gather, params)
                        gathered = strategy.exchange(exp, full,
                                                     rows(nbr_idx))
                    params = aggregate(rows, params, gathered,
                                       rows(link_full))
                if caps.grad_exchange:
                    rng, sub = jax.random.split(rng)
                    if sparse:
                        params = gradient_exchange(
                            ctx, params, link_u,
                            _and_masks(ev.live if has_dyn else None, arr_e),
                            round_idx, sub)
                    else:
                        params = gradient_exchange(rows, params,
                                                   rows(link_full),
                                                   round_idx, sub)
            # kind == "none": isolation — no communication at all.
        elif per_edge:
            # per-EDGE transport: every directed link carries its own
            # reference/residual/threshold; the full link mask feeds the
            # exchange (link-layer ack through the layout swap) and the
            # transport hands back both the receiver-layout gathered models
            # (fresh or per-link stale cache) and the aggregation mask.
            if transport.wants_rng:
                rng, ck = jax.random.split(rng)
            else:
                ck = None
            if sparse:
                # flat [E] path: a CSR directed edge id is both the sender-
                # and receiver-layout address of its link, so participation
                # draws, liveness and rejoin resets compose per edge id and
                # the transport returns the per-edge reconstruction bank
                # the SparseNeighborhood addresses by CSR position — no
                # layout swap, no reverse-slot gather.
                link_e = (jnp.ones((plan.num_directed,), jnp.float32)
                          if link_u is None
                          else (link_u < cfg.participation).astype(
                              jnp.float32))
                if has_dyn:
                    rj = ev.rejoined
                    reset = jnp.maximum(rj[edge_src], rj[edge_dst])
                    live = ev.live
                    link_e = link_e * live
                else:
                    reset = live = None
                if arr_e is not None:
                    # a late payload is a failed link: the receiver's
                    # per-edge cache freezes and its bank serves the stale
                    # (or dropped) reconstruction, bit-identically.
                    link_e = link_e * arr_e
                with jax.named_scope(EXCHANGE):
                    edge_table, mask_e, gate_full, new_comm = \
                        transport.exchange(params, comm_state, link_e, ck,
                                           live=live, reset=reset, ctx=ctx,
                                           wire=wire)
                # participation/liveness/gates are already folded into the
                # [E] masks, so the view gets no gate_vec/link_u of its own.
                with jax.named_scope(AGGREGATE):
                    local_mat, unflatten = tree_flatten_stacked(params)
                    pod = ctx.pod if ctx.pod is not None else jnp.int32(0)
                    nb = SparseNeighborhood(
                        plan, pod, None, local_mat, unflatten, None, None,
                        1.0, edge_table=edge_table, edge_mask=mask_e)
                    params = strategy.flat_aggregate(
                        exp, jax.tree.map(rows, agg_state), nb)
                if has_obs:
                    obs_fired = gate_full
                    obs_deliv = gate_full * link_e
            else:
                if has_dyn:
                    rj = ev.rejoined
                    reset = jnp.maximum(rj[:, None], rj[nbr_idx]) * nbr_valid
                    live = ev.live
                else:
                    reset = live = None
                with jax.named_scope(EXCHANGE):
                    gathered, mask, gate_full, new_comm = transport.exchange(
                        params, comm_state, link_full, ck, live=live,
                        reset=reset, ctx=ctx, wire=wire)
                if use_flat:
                    # flat form over the transport's pre-gathered per-link
                    # panel (no single [N, D] table exists: slot models are
                    # per-link stale caches), composed weights ω·|D|·mask —
                    # the same kernel reduce as the per-node path, so
                    # fp32/thr0 stays bit-exact against it.
                    with jax.named_scope(AGGREGATE):
                        local_mat, unflatten = tree_flatten_stacked(params)
                        panel = jnp.concatenate(
                            [l.reshape(l.shape[0], l.shape[1], -1)
                              .astype(jnp.float32)
                             for l in jax.tree.leaves(gathered)], axis=2)
                        nb = DenseNeighborhood(None, None,
                                               rows(nbr_weight) * mask,
                                               local_mat, unflatten,
                                               panel=panel)
                        params = strategy.flat_aggregate(
                            exp, jax.tree.map(rows, agg_state), nb)
                else:
                    params = aggregate(rows, params, gathered, mask)
                if has_obs:
                    obs_fired = transport.recv_layout(gate_full)
                    obs_deliv = obs_fired * link_full
            # unicast accounting: one payload per FIRED edge (a silent edge
            # of an otherwise-sending node costs nothing); failed links
            # still burn the sender's bytes.
            sent_edges = jnp.sum(gate_full)
            if has_dyn:
                trig = sent_edges / jnp.maximum(jnp.sum(ev.live), 1.0)
            else:
                trig = sent_edges / jnp.float32(transport.num_edges)
        else:
            # per-NODE transport: encode -> (event-triggered, possibly
            # failing) wire -> decode -> aggregate.  With the fp32 codec
            # and threshold 0 this is bit-for-bit the plain round (same rng
            # stream, identical payload values).
            if transport.wants_rng:
                rng, ck = jax.random.split(rng)
            else:
                ck = None
            if has_dyn:
                # a rejoined node's row returns to bootstrap before the
                # exchange; dead senders are vetoed outright.
                comm_state = transport.reset_rows(comm_state, ev.rejoined,
                                                  ctx=ctx)
                send_mask = rows(ev.alive)
            else:
                send_mask = None
            with jax.named_scope(EXCHANGE):
                decoded, gate_full, new_comm = transport.exchange(
                    params, comm_state, ck, send_mask=send_mask, ctx=ctx,
                    wire=wire)
            # `decoded` rows of silent nodes hold their cached last-sent
            # model, so "stale" aggregates them at full weight — masking
            # only edges that have NEVER DELIVERED, whose receiver-side
            # cache is still the zero bootstrap reference.  Delivery, not
            # transmission: a payload sent into a dead/failed/late link
            # never reached this receiver, so `ever_recv` must not flip
            # (the old `ever_sent` gate flipped on send and let receivers
            # aggregate bootstrap zeros as if they were models).  "drop"
            # masks any silent or undelivered edge like a failed link.
            stale = transport.config.on_silence != "drop"
            if sparse:
                live_e = ev.live if has_dyn else None
                # current-round exogenous edge factors (participation is
                # applied inside the Neighborhood view via link_u)
                cur_e = _and_masks(live_e, arr_e)
                part_e = ((link_u < cfg.participation).astype(jnp.float32)
                          if link_u is not None else None)
                with jax.named_scope(EXCHANGE):
                    delivered_e = _and_masks(gate_full[edge_src], part_e,
                                             live_e, arr_e)
                    new_comm = transport.note_delivery(new_comm, delivered_e)
                if has_obs:
                    obs_fired = (gate_full[edge_src] * ev.live if has_dyn
                                 else gate_full[edge_src])
                    obs_deliv = delivered_e
                if stale:
                    params = flat_gossip(
                        params, None,
                        table=decoded,
                        edge_mask=_and_masks(cur_e, new_comm.ever_recv))
                else:
                    params = flat_gossip(
                        params, gate_full,
                        table=decoded,
                        edge_mask=cur_e)
            else:
                with jax.named_scope(EXCHANGE):
                    delivered_full = edge_delivery(gate_full, link_full,
                                                   nbr_idx)
                    new_comm = transport.note_delivery(new_comm,
                                                       delivered_full)
                if has_obs:
                    obs_fired = gate_full[nbr_idx] * (ev.live if has_dyn
                                                      else nbr_valid)
                    obs_deliv = delivered_full
                if stale:
                    mask_full = link_full * new_comm.ever_recv
                else:
                    mask_full = delivered_full
                if use_flat:
                    params = flat_gossip(
                        params, None,
                        table=decoded,
                        mask_full=mask_full)
                else:
                    with jax.named_scope(EXCHANGE):
                        gathered = strategy.exchange(exp, decoded,
                                                     rows(nbr_idx))
                    params = aggregate(rows, params, gathered,
                                       rows(mask_full))
            # broadcast accounting: a transmitting node pays one payload
            # per outgoing edge — its LIVE outgoing edges under dynamics (a
            # non-existent link carries nothing); failed links still burn
            # the sender's bytes.
            if has_dyn:
                if sparse:
                    # Σ_e gate[src_e]·live_e — the flat-edge form of the
                    # dense gate·live_outdeg sum (both are sums of exact
                    # small integers, so f32 accumulates them exactly).
                    sent_edges = jnp.sum(gate_full[edge_src] * ev.live)
                else:
                    live_deg = jnp.sum(ev.live, axis=1)
                    sent_edges = jnp.sum(gate_full * live_deg)
                trig = sent_edges / jnp.maximum(jnp.sum(ev.live), 1.0)
            else:
                sent_edges = jnp.sum(gate_full * degrees)
                trig = sent_edges / total_edges

        # -- dynamics epilogue: freeze the dead, count the live ------------
        if has_dyn:
            params = _freeze_dead(params, old_params, rows(ev.alive))
            live_total = jnp.sum(ev.live)
        else:
            live_total = None

        # -- event-clock epilogue: advance the simulated clock -------------
        # Deadline mode ticks by exactly `deadline` (the round IS the tick);
        # synchronous mode ticks by the realized makespan — the slowest
        # node's compute, stretched to the slowest LIVE link's landing time
        # when the round exchanges payloads (everyone waits for everyone:
        # that is the cost the deadline frontier is measured against).
        if has_time:
            if deadline is not None:
                tick = jnp.float32(deadline)
            else:
                tick = jnp.max(t_cost)
                if exchanges:
                    if sparse:
                        lv = (ev.live if has_dyn
                              else jnp.ones_like(transfer_e))
                        land = lv * (t_cost[edge_src] + transfer_e)
                    else:
                        lv = ev.live if has_dyn else nbr_valid
                        land = lv * (t_cost[nbr_idx] + transfer_panel)
                    tick = jnp.maximum(tick, jnp.max(land))
            sim_t = time_state.t + tick
            if deadline is not None:
                if sparse:
                    arr_live = (arr_e * ev.live if has_dyn else arr_e)
                else:
                    arr_live = (arr_full * ev.live if has_dyn
                                else arr_full)
                arrived = jnp.sum(arr_live)
            else:
                # no deadline: every live edge's payload arrives
                arrived = (jnp.sum(ev.live) if has_dyn else total_edges)
            new_time = TimingState(t=sim_t, last_cost=t_cost)
        else:
            sim_t = arrived = new_time = None

        # -- telemetry epilogue: channel arithmetic on the carried dict ----
        # Pure full-axis arithmetic over quantities the round already
        # computed (no rng, no extra collectives — the params-reading
        # consensus/drift probes live OUTSIDE the round, gated to eval
        # rounds by the runner), so `telemetry=None` stays bit-identical
        # by construction.
        if has_obs:
            obs_state, obs_out = tele.step(
                obs_state, budgets=budgets_full, t_cost=t_cost,
                fired=obs_fired, delivered=obs_deliv)
        else:
            obs_out = None

        return (params, opt, new_comm, dyn_state, new_time, obs_state, rng,
                train_loss, sent_edges, trig, live_total, sim_t, arrived,
                obs_out)

    return body


def _squeeze(out):
    """Drop the None slots of the full 14-tuple, yielding the documented
    per-configuration calling convention (the slot ORDER is fixed, so the
    surviving entries line up with the module-docstring signatures)."""
    return tuple(o for o in out if o is not None)


def _unpack_states(exp, rest):
    """Split a round_fn's positional tail ``(*states, round_idx, rng)``
    into the body's fixed slots, with None for the states the experiment
    does not carry.  States appear in (comm, dyn, time, obs) order."""
    rest = list(rest)
    comm_state = rest.pop(0) if exp.transport is not None else None
    dyn_state = rest.pop(0) if exp.bound_dyn is not None else None
    time_state = rest.pop(0) if exp.bound_timing is not None else None
    obs_state = rest.pop(0) if exp.bound_obs is not None else None
    round_idx, rng = rest
    return comm_state, dyn_state, time_state, obs_state, round_idx, rng


# ------------------------------------------------------------- vmap backend

def _build_vmap_round(exp):
    """The dense lowering: the round body under the identity context."""
    body = _make_round_body(exp, loss_reduce=_identity_rows)
    x, y = exp.x_shards, exp.y_shards

    def round_fn(params, opt, *rest):
        comm_state, dyn_state, time_state, obs_state, round_idx, rng = \
            _unpack_states(exp, rest)
        return _squeeze(body(DENSE_CTX, params, opt, comm_state, dyn_state,
                             time_state, obs_state, round_idx, rng, x, y))

    return round_fn


# -------------------------------------------------------- shard_map backend

def _build_shardmap_round(exp):
    """The same round body shard_mapped over the pod axis.

    All mesh axes are manual (`check_vma=False`) following
    `repro.dist.dfl_step.build_dfl_round_shardmap`; each pod holds its
    nodes' full replicas, so per-node reductions (Eq. 5's global norm, the
    trigger's drift) are complete blockwise and only the exchange's gather
    crosses pods.  Transport state splits by the transport's `state_specs`:
    sender-private rows (residuals, per-edge thresholds/EMAs) shard with
    their pod; receiver-facing caches (`last_sent`, the ever-sent/-delivered
    flags) are replicated and recomputed identically on every pod from the
    gathered wire, which is what lets the per-edge reverse-slot gather and
    the CFA-GE neighbour walk run blockwise.
    """
    mesh = exp.mesh
    if mesh is None or NODE_AXIS not in mesh.shape:
        raise ValueError(
            f"backend 'shard_map' needs a mesh with a {NODE_AXIS!r} axis; "
            f"pass mesh= or use backend='vmap'")
    n = exp.n
    n_pods = int(mesh.shape[NODE_AXIS])
    if n % n_pods:
        raise ValueError(f"{n} DFL nodes do not tile the {n_pods}-pod axis")
    per_pod = n // n_pods
    transport = exp.transport
    has_comm = transport is not None
    has_dyn = exp.bound_dyn is not None
    has_time = exp.bound_timing is not None
    has_obs = exp.bound_obs is not None

    def pmean(v):
        return jax.lax.pmean(v, NODE_AXIS)

    body = _make_round_body(exp, loss_reduce=pmean)

    def make_ctx():
        pod = jax.lax.axis_index(NODE_AXIS)
        i0 = pod * per_pod

        def rows(a):
            return jax.lax.dynamic_slice_in_dim(a, i0, per_pod, axis=0)

        def gather(a):
            return jax.lax.all_gather(a, NODE_AXIS, axis=0, tiled=True)

        return PodContext(rows=rows, gather=gather, pod=pod)

    shard = P(NODE_AXIS)
    rep = P()
    # State specs in (comm, dyn, time, obs) order.  Dynamics state, the
    # TimingState (scalar clock + [N] last-cost) and the telemetry
    # accumulator dict (full-axis channel sums) are fully replicated:
    # every pod advances them identically from replicated rng/masks, the
    # same discipline that keeps the backends bit-identical everywhere
    # else.  Transport state splits by the transport's own `state_specs`;
    # the single `rep` spec is a pytree PREFIX covering every leaf of the
    # telemetry dict.
    state_specs = []
    if has_comm:
        state_specs.append(transport.state_specs(shard, rep))
    if has_dyn:
        state_specs.append(rep)
    if has_time:
        state_specs.append(rep)
    if has_obs:
        state_specs.append(rep)
    state_specs = tuple(state_specs)
    # Replicated extras past (rng, loss):
    # (sent, trig | live | sim_t, arr | obs_snapshot).
    n_extras = 2 * has_comm + has_dyn + 2 * has_time + has_obs

    def block(params, opt, *rest):
        comm_state, dyn_state, time_state, obs_state, round_idx, rng = \
            _unpack_states(exp, rest[:-2])
        x, y = rest[-2:]
        return _squeeze(body(make_ctx(), params, opt, comm_state, dyn_state,
                             time_state, obs_state, round_idx, rng, x, y))

    sharded = jax.shard_map(
        block, mesh=mesh,
        in_specs=(shard, shard) + state_specs + (rep, rep, shard, shard),
        out_specs=((shard, shard) + state_specs + (rep, rep)
                   + (rep,) * n_extras),
        check_vma=False)

    def round_fn(params, opt, *rest):
        return sharded(params, opt, *rest, exp.x_shards, exp.y_shards)

    return round_fn
