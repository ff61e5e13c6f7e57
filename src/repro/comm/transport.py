"""Gossip transport: codecs x event trigger x exact bytes-on-wire accounting.

Sits between local training and aggregation.  Each round every node:

  1. measures its drift ||w_i - w^last_sent|| and decides whether to
     transmit (trigger module; threshold 0 = always send),
  2. if transmitting, encodes its payload — delta codecs (int8, top-k)
     compress the drift plus the carried error-feedback residual,
     dense codecs (fp32, bf16) the model itself,
  3. receivers dequantize first and aggregate second, so DecDiff's Eq. 5-6
     semantics are untouched: the aggregator simply sees ŵ_j instead of w_j.

Three transports share the codecs and that round shape:

`GossipTransport` — per-NODE state (the PR-2 broadcast model): one
`last_sent[j]` [N, D] doubles as sender j's trigger reference AND every
receiver's cached copy of j's reconstruction, one shared residual per node.
A node encodes once and broadcasts the same payload on all its edges.

`EdgeGossipTransport` — per-EDGE state in the padded-neighbour layout
(`[N, max_deg, ...]`): each directed link (i -> j) keeps its own
`last_sent[i, d]`, error-feedback `residual[i, d]`, adaptive `threshold
[i, d]` and drift EMA, where d is j's slot in i's neighbour list.  The
payload for each edge is encoded against *that edge's* reference, and —
the point of the exercise — state only advances on links that actually
delivered: a Bernoulli link failure on (i, j) leaves both (i, j)'s and
(i, k)'s residuals bit-identical to their no-traffic values instead of
poisoning a shared top-k error-feedback buffer for every neighbour.  The
receiver-side cache interpretation is exact: `last_sent[i, d]` IS what the
receiver on that edge holds (the per-node transport loses this the moment
one link drops), so "stale" aggregation serves genuinely per-link staleness.
Cost: encode runs per edge, not per node, and state is max_deg x larger —
the price of personalized links (the wire bytes are identical when all
edges of a node fire together).

`SparseEdgeGossipTransport` — the same per-edge semantics re-keyed to the
flat `[E]` CSR edge list of a `SparseTopology`: state is O(E) not
O(N·max_deg), there is no padding, no layout swap and no reverse-slot
gather (a CSR edge id addresses BOTH directions of the exchange), and the
per-edge rng stream is keyed by the same canonical directed-edge
enumeration the dense transport's slot panel indexes — which is what makes
the two layouts bit-identical on the same graph.

The ONE exchange path (every backend, every transport)
------------------------------------------------------

`exchange` is written once against a :class:`PodContext` — the pair of
(row-slice, all-gather) primitives that describe where the caller's block
of sender rows sits in the full node axis:

  * ``DENSE_CTX`` (the default) is the identity pair: the caller holds all
    N rows, nothing moves — the vmap backend and every direct caller;
  * the engine's shard_map backend passes a context whose ``rows`` slices
    the pod's block out of replicated [N, ...] quantities and whose
    ``gather`` is the tiled `all_gather` over the pod axis.

Sender-private state (error-feedback residuals, per-edge thresholds and
drift EMAs) lives in block rows and shards with its pod; receiver-facing
state (the `last_sent` reconstruction caches, the ever-sent/-delivered
flags) is REPLICATED: every pod recomputes the full-axis update from the
gathered wire deterministically, so the replicas cannot diverge and the
reverse-slot gather (receiver r reads sender j's slot toward r — resolved
by one row gather over the flattened per-link table) never crosses pods at aggregation time.  `state_specs` hands the
engine the matching PartitionSpec tree.

What the gather carries is the `wire` choice: ``"encoded"`` (the default)
moves the codec payload — int8 crosses the interconnect at 1/4 the fp32
footprint and every pod decodes the same bytes — while ``"decoded"`` moves
the reconstructed fp32 rows (the small-N oracle).  decode(encode(x)) is
deterministic, so the two wires are bit-identical by construction (pinned
in tests/test_engine.py); only bandwidth differs.

Thresholds are either `fixed` (the scalar `trigger_threshold` on every
edge) or `adaptive`: a per-edge Robbins-Monro controller tracks the
(1 - target_trigger)-quantile of that edge's drift so each link's long-run
triggered fraction converges to `target_trigger` (see trigger.py).

Accounting is exact and static: `payload_bytes` is the serialized size of
one payload (codec.payload_bytes_for).  Bytes-on-wire per round is
payload_bytes x (number of fired edges) — per-node: Σ_i gate_i x outdeg_i;
per-edge: Σ_ij gate_ij.  Failed links still burn the sender's bytes (the
sender cannot know *at send time*), they just deliver nothing; the per-edge
transport additionally models a link-layer ack, which is how it knows not
to advance a dropped link's reference.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.codecs import Codec, make_codec
from repro.comm.trigger import (
    adaptive_threshold_update,
    drift_gate,
    edge_drift_gate,
)
from repro.utils.pytree import tree_flatten_stacked

POLICIES = ("fixed", "adaptive")
WIRES = ("encoded", "decoded")


class PodContext(NamedTuple):
    """Where the caller's block of sender rows sits in the full node axis.

    ``rows``   maps a replicated [N, ...] quantity to the caller's [R, ...]
               block (identity when the caller holds all rows);
    ``gather`` maps the caller's [R, ...] block to the full [N, ...] axis
               (the engine's tiled all_gather over the pod mesh axis;
               identity on the dense path);
    ``pod``    the caller's block index along the pod mesh axis (a traced
               scalar under shard_map; None on the single-block path).
    """

    rows: Callable
    gather: Callable
    pod: Optional[jnp.ndarray] = None


def _identity(a):
    return a


#: The dense (single-block) context: R == N, nothing moves.
DENSE_CTX = PodContext(rows=_identity, gather=_identity)


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Transport knobs, carried on Experiment(comm=...).

    codec: "fp32" | "bf16" | "int8" | "topk".
    trigger_threshold: L2 drift below which a sender stays silent (0 = the
      legacy always-send behaviour, bit-for-bit).  Used by the "fixed"
      policy; the "adaptive" policy learns per-edge thresholds instead.
    policy: "fixed" (one scalar threshold everywhere) or "adaptive"
      (per-edge drift-rate-controlled thresholds; implies per-edge state).
    per_edge: keep transport state per directed link `[N, max_deg, ...]`
      instead of per node — independent error-feedback residuals and
      staleness per link, surviving Bernoulli link failures independently.
      Forced on by policy="adaptive".
    target_trigger: adaptive policy's per-edge long-run triggered fraction
      target, in (0, 1].
    drift_ema_beta: decay of the per-edge drift EMA that scales the
      adaptive controller's step.
    threshold_rate: adaptive controller gain.
    topk_ratio: fraction of coordinates the top-k codec ships.
    topk_momentum: momentum-masked top-k selection (0 = plain magnitude
      top-k); see codecs.TopKCodec.
    stochastic: int8 rounding mode (True = unbiased stochastic rounding;
      False = deterministic nearest, needed for vmap/shard_map equality).
    on_silence: what receivers aggregate for a neighbour whose trigger did
      not fire.  "stale" (default, the Zehtabi et al. event-triggered DFL
      semantics): its cached last-transmitted model — silence means "use
      what you have", costs nothing, and degrades convergence more
      gracefully than dropping (staleness still drags; see the BENCH_comm
      frontier for the measured accuracy-vs-bytes tradeoff per threshold).
      "drop": mask the neighbour out entirely, like a failed link.
      Exogenous link failures always drop (a loss, not a decision).
    """

    codec: str = "fp32"
    trigger_threshold: float = 0.0
    policy: str = "fixed"
    per_edge: bool = False
    target_trigger: float = 0.5
    drift_ema_beta: float = 0.9
    threshold_rate: float = 0.5
    topk_ratio: float = 0.01
    topk_momentum: float = 0.0
    stochastic: bool = True
    on_silence: str = "stale"

    def __post_init__(self):
        if self.on_silence not in ("stale", "drop"):
            raise ValueError(f"on_silence must be 'stale' or 'drop', "
                             f"got {self.on_silence!r}")
        if self.policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, "
                             f"got {self.policy!r}")
        if self.policy == "adaptive" and not (0.0 < self.target_trigger <= 1.0):
            raise ValueError(f"target_trigger must be in (0, 1], "
                             f"got {self.target_trigger}")

    @property
    def use_per_edge(self) -> bool:
        """Per-edge state is explicit (`per_edge`) or implied by the
        adaptive policy (per-edge thresholds need per-edge references)."""
        return self.per_edge or self.policy == "adaptive"

    def make_codec(self) -> Codec:
        kwargs = {}
        if self.codec == "topk":
            kwargs["ratio"] = self.topk_ratio
            if self.topk_momentum > 0:
                kwargs["momentum"] = self.topk_momentum
        if self.codec == "int8":
            kwargs["stochastic"] = self.stochastic
        return make_codec(self.codec, **kwargs)


class CommState(NamedTuple):
    """Per-node transport state, threaded through the jitted round.

    `last_sent`, `ever_sent` and `ever_recv` are receiver-facing: replicated
    over pods (every pod recomputes the full-axis update from the gathered
    wire); `residual` is sender-private and shards with its rows.

    `ever_recv` is the per-EDGE delivery history (`[N, max_deg]` in the
    padded receiver layout, `[E]` over the CSR edge list — whichever layout
    the engine bound): has this edge ever actually DELIVERED a payload?  It
    is what the `on_silence="stale"` mask consults — a payload that was
    *sent but never arrived* (link failure, missed deadline) leaves it 0, so
    the receiver does not aggregate a cache it never filled.  `ever_sent`
    (sender-side, flips on transmission) is kept for byte/trigger
    accounting; it must NOT gate staleness.  `ever_recv` is None when the
    transport is built without an edge layout (direct construction) — the
    engine always supplies one.
    """

    last_sent: jnp.ndarray            # [N, D] last reconstruction on the wire
    residual: Optional[jnp.ndarray]   # [R, ...] EF residual (None if stateless)
    ever_sent: jnp.ndarray            # [N] {0,1}: has node i transmitted yet?
    ever_recv: Optional[jnp.ndarray] = None  # [N, max_deg] or [E] {0,1}


class EdgeCommState(NamedTuple):
    """Per-EDGE transport state, `[*, max_deg, ...]` padded-neighbour layout.

    Slot d of node i is the directed link i -> nbr_idx[i, d]; padding slots
    exist but never fire and never update.  `last_sent` and `ever_delivered`
    are receiver-facing (replicated over pods); the residual, threshold and
    drift-EMA rows are sender-private and shard with their pod.
    """

    last_sent: jnp.ndarray            # [N, E, D] per-link reconstruction ref
    residual: Optional[jnp.ndarray]   # [R, E, ...] per-link EF residual
    threshold: jnp.ndarray            # [R, E] per-link trigger thresholds
    drift_ema: jnp.ndarray            # [R, E] per-link drift EMA (adaptive)
    ever_delivered: jnp.ndarray       # [N, E] {0,1}: link ever delivered?


def _check_wire(wire: str):
    if wire not in WIRES:
        raise ValueError(f"wire must be one of {WIRES}, got {wire!r}")


class GossipTransport:
    """Flatten -> trigger -> encode -> wire -> decode -> unflatten.

    The optional edge-layout kwargs give the per-node transport a per-EDGE
    delivery history (`CommState.ever_recv`) in the engine's bound layout:
    pass `nbr_idx`/`nbr_valid` (the padded `[N, max_deg]` panels) on the
    dense layout, or `edge_src`/`edge_dst` (the CSR directed edge list) on
    the sparse one.  Without either the transport still runs (direct
    construction, the legacy shape) but carries no delivery history —
    `ever_recv` stays None."""

    def __init__(self, config: CommConfig, stacked_params, *,
                 nbr_idx=None, nbr_valid=None, edge_src=None, edge_dst=None):
        self.config = config
        self.codec = config.make_codec()
        mat, self._unflatten = tree_flatten_stacked(stacked_params)
        self.n, self.d = int(mat.shape[0]), int(mat.shape[1])
        # exact serialized payload size for ONE node's transmission
        self.payload_bytes = self.codec.payload_bytes_for(self.d)
        self.dense_bytes = 4 * self.d  # fp32 reference for reduction ratios
        self.wants_rng = (self.codec.needs_rng
                          and getattr(self.codec, "stochastic", True))
        if nbr_idx is not None:
            idx = np.asarray(nbr_idx, np.int64)
            self._recv_idx = jnp.asarray(np.maximum(idx, 0).astype(np.int32))
            self._recv_valid = jnp.asarray(
                np.asarray(nbr_valid, np.float32))
            self._recv_shape = self._recv_idx.shape
            self._edge_src = self._edge_dst = None
        elif edge_src is not None:
            self._edge_src = jnp.asarray(np.asarray(edge_src, np.int32))
            self._edge_dst = jnp.asarray(np.asarray(edge_dst, np.int32))
            self._recv_shape = self._edge_src.shape
            self._recv_idx = self._recv_valid = None
        else:
            self._recv_shape = None
            self._recv_idx = self._recv_valid = None
            self._edge_src = self._edge_dst = None

    def init_state(self, stacked_params) -> CommState:
        mat, _ = tree_flatten_stacked(stacked_params)
        residual = (jax.vmap(self.codec.init_residual)(mat)
                    if self.codec.has_residual else None)
        ever_recv = (jnp.zeros(self._recv_shape, jnp.float32)
                     if self._recv_shape is not None else None)
        # zero reference: the first transmission carries the full model
        # through the codec, so receivers need no out-of-band bootstrap.
        return CommState(last_sent=jnp.zeros_like(mat), residual=residual,
                         ever_sent=jnp.zeros((self.n,), jnp.float32),
                         ever_recv=ever_recv)

    def state_specs(self, shard, rep) -> CommState:
        """The PartitionSpec tree matching init_state's layout: replicated
        receiver-facing caches, sharded sender-private residual rows."""
        return CommState(
            last_sent=rep,
            residual=shard if self.codec.has_residual else None,
            ever_sent=rep,
            ever_recv=rep if self._recv_shape is not None else None)

    def note_delivery(self, state: CommState, delivered) -> CommState:
        """Fold one round's REALIZED deliveries (`[N, max_deg]` or `[E]`
        {0,1} in the bound layout: trigger AND link AND live AND arrival)
        into the per-edge delivery history.  Kept separate from `exchange`
        because only the engine knows the composed delivery mask — the
        transport sees the trigger gate, not the deadline."""
        if state.ever_recv is None:
            return state
        return state._replace(
            ever_recv=jnp.maximum(state.ever_recv, delivered))

    def reset_rows(self, state: CommState, reset,
                   ctx: PodContext = DENSE_CTX) -> CommState:
        """Rows where `reset` ([N] {0,1}) > 0 return to the zero bootstrap
        (reference, residual, ever_sent all cleared) — the defined semantics
        for a device that churned out and rejoined: it is a FRESH device, so
        its receivers' cached reconstruction of it is gone and its next
        transmission carries the full model through delta codecs again.
        (The per-node state conflates the sender reference with every
        receiver's cache, so a reset clears both; the per-edge transport
        resolves them per link — see EdgeGossipTransport.reset_edges.)
        A zero `reset` row is left bit-identical."""
        r = reset > 0
        residual = state.residual
        if residual is not None:
            rr = ctx.rows(reset) > 0
            rb = rr.reshape(rr.shape + (1,) * (residual.ndim - 1))
            residual = jnp.where(rb, 0.0, residual)
        ever_recv = state.ever_recv
        if ever_recv is not None:
            # every edge incident to a reset node (either direction) loses
            # its delivery history: the rejoined device's caches of its
            # peers AND its peers' caches of it are gone.
            if self._recv_idx is not None:
                clear = jnp.maximum(reset[:, None],
                                    reset[self._recv_idx]) * self._recv_valid
            else:
                clear = jnp.maximum(reset[self._edge_src],
                                    reset[self._edge_dst])
            ever_recv = jnp.where(clear > 0, 0.0, ever_recv)
        return CommState(
            last_sent=jnp.where(r[:, None], 0.0, state.last_sent),
            residual=residual,
            ever_sent=jnp.where(r, 0.0, state.ever_sent),
            ever_recv=ever_recv)

    def exchange(self, stacked_params, state: CommState, rng=None,
                 send_mask=None, *, ctx: PodContext = DENSE_CTX,
                 wire: str = "encoded"):
        """One transport round for the caller's block of sender rows.

        Args:
          stacked_params: pytree, leaves [R, ...] — the block's models (all
            N rows on the dense context).
          state: CommState (replicated caches + this block's residual rows).
          rng: PRNG key when the codec wants one — consumed REPLICATED over
            the full node axis and row-sliced, so every block draws the
            same per-node key regardless of where the rows live.
          send_mask: optional [R] {0,1} sender veto regardless of drift (a
            churned-out device transmits nothing and its state freezes).
          ctx: the block's PodContext (see module docstring).
          wire: "encoded" gathers the codec payload (every pod decodes the
            same bytes), "decoded" gathers the reconstructed rows — the
            dense oracle.  Bit-identical by construction.

        Returns (decoded_models, gate_full, new_state):
          decoded_models — pytree with leaves [N, ...]: for each sender the
            model its neighbours reconstruct this round (rows of silent
            nodes hold their previous reconstruction; the aggregation mask
            zeroes them out anyway),
          gate_full — [N] {0,1} who transmitted (replicated),
          new_state — the threaded CommState.
        """
        _check_wire(wire)
        codec = self.codec
        w, _ = tree_flatten_stacked(stacked_params)
        r = int(w.shape[0])
        if self.wants_rng:
            if rng is None:
                raise ValueError(f"codec {codec.name!r} needs an rng key")
            keys = ctx.rows(jax.random.split(rng, self.n))
        else:
            keys = jnp.zeros((r, 2), jnp.uint32)

        last_full = state.last_sent
        last = ctx.rows(last_full)
        gate, _ = drift_gate(w, last, self.config.trigger_threshold)
        if send_mask is not None:
            gate = gate * send_mask
        x = w - last if codec.is_delta else w

        def enc(xi, key, res):
            return codec.encode(xi, rng=key if self.wants_rng else None,
                                residual=res)

        if codec.has_residual:
            payload, new_res = jax.vmap(enc)(x, keys, state.residual)
        else:
            payload, _ = jax.vmap(lambda xi, key: enc(xi, key, None))(x, keys)
            new_res = None

        def dec(p):
            return codec.decode(p, out_size=self.d)

        if wire == "encoded":
            dec_full = jax.vmap(dec)(jax.tree.map(ctx.gather, payload))
        else:
            dec_full = ctx.gather(jax.vmap(dec)(payload))
        gate_full = ctx.gather(gate)

        recon = last_full + dec_full if codec.is_delta else dec_full
        new_last = jnp.where(gate_full[:, None] > 0, recon, last_full)
        if codec.has_residual:
            # a silent node keeps accumulating: its un-flushed residual
            # stays put until the trigger fires again.
            keep = gate.reshape((r,) + (1,) * (new_res.ndim - 1)) > 0
            new_res = jnp.where(keep, new_res, state.residual)
        new_state = CommState(
            last_sent=new_last, residual=new_res,
            ever_sent=jnp.maximum(state.ever_sent, gate_full),
            ever_recv=state.ever_recv)  # the engine folds realized
        # deliveries in afterwards (note_delivery) — exchange cannot know
        # the composed link x live x arrival mask.
        return self._unflatten(new_last), gate_full, new_state


class EdgeGossipTransport:
    """Per-edge transport: one (reference, residual, threshold) per link.

    Construction takes the graph's padded-neighbour layout (`nbr_idx`
    [N, E] int with -1 padding, `nbr_valid` [N, E] {0,1}) because per-edge
    state is keyed by (sender, slot) and the receiver-side gather needs the
    *reverse* slot map: receiver r hearing neighbour j at slot e reads
    sender j's edge state at slot rev[r, e] (the slot of r in j's list).
    The gather itself — receiver rows out of the flattened [N*E, D]
    per-link reference table — is one XLA row gather (`jnp.take`) on every
    backend (a pure copy, bitwise identical to fancy indexing).
    """

    def __init__(self, config: CommConfig, stacked_params,
                 nbr_idx: np.ndarray, nbr_valid: np.ndarray):
        self.config = config
        self.codec = config.make_codec()
        mat, self._unflatten = tree_flatten_stacked(stacked_params)
        self.n, self.d = int(mat.shape[0]), int(mat.shape[1])
        self.e = int(nbr_idx.shape[1])
        self.payload_bytes = self.codec.payload_bytes_for(self.d)
        self.dense_bytes = 4 * self.d
        self.wants_rng = (self.codec.needs_rng
                          and getattr(self.codec, "stochastic", True))

        idx = np.asarray(nbr_idx, np.int64)
        valid = np.asarray(nbr_valid, np.float32)
        # reverse slot map: rev[r, e] = d s.t. nbr_idx[j, d] == r for
        # j = nbr_idx[r, e] (exists for every valid slot: undirected graph).
        rev = np.zeros((self.n, self.e), np.int32)
        for r in range(self.n):
            for e in range(self.e):
                j = idx[r, e]
                if j < 0:
                    continue
                (slots,) = np.nonzero(idx[j] == r)
                if slots.size == 0:
                    raise ValueError(
                        f"neighbour layout not symmetric: {r} lists {j} but "
                        f"{j} does not list {r} — per-edge state needs an "
                        f"undirected graph")
                rev[r, e] = int(slots[0])
        self.nbr_idx = jnp.asarray(np.maximum(idx, 0).astype(np.int32))
        self.nbr_valid = jnp.asarray(valid)
        self.rev_slot = jnp.asarray(rev)
        self.num_edges = float(valid.sum())  # directed edge count
        # canonical CSR directed-edge id of the link (i -> j) at sender slot
        # (i, d): receiver j's row offset plus i's position among j's senders
        # (ascending — the padded lists are sorted, so rev IS that position).
        # This is the exact enumeration SparseTopology sorts its edge list
        # by, which is what lets the sparse per-edge transport consume the
        # identical per-edge rng stream.  Padding slots alias edge 0; their
        # keys are drawn but never gate an update.
        deg = valid.sum(axis=1).astype(np.int64)
        offsets = np.concatenate([np.zeros(1, np.int64), np.cumsum(deg)])
        self.num_directed = int(deg.sum())
        self.edge_id = jnp.asarray(
            (offsets[np.maximum(idx, 0)] + rev).astype(np.int32))
        # the threshold an edge (re)starts from: the scalar for the fixed
        # policy, the always-send bootstrap for the adaptive one (shared by
        # init_state and reset_edges so a rejoined device re-bootstraps
        # exactly like a fresh one)
        self.thr0 = (config.trigger_threshold if config.policy == "fixed"
                     else 0.0)

    def init_state(self, stacked_params) -> EdgeCommState:
        mat, _ = tree_flatten_stacked(stacked_params)
        zeros_edges = jnp.zeros((self.n, self.e, self.d), jnp.float32)
        if self.codec.has_residual:
            res0 = self.codec.init_residual(mat[0])
            residual = jnp.zeros((self.n, self.e) + res0.shape, jnp.float32)
        else:
            residual = None
        # fixed policy: the scalar threshold on every edge; adaptive: start
        # at 0 (always-send bootstrap — the first payloads carry the full
        # model through delta codecs) and let the controller raise it.
        return EdgeCommState(
            last_sent=zeros_edges,
            residual=residual,
            threshold=jnp.full((self.n, self.e), self.thr0, jnp.float32),
            drift_ema=jnp.zeros((self.n, self.e), jnp.float32),
            ever_delivered=jnp.zeros((self.n, self.e), jnp.float32),
        )

    def state_specs(self, shard, rep) -> EdgeCommState:
        """The PartitionSpec tree matching init_state's layout: replicated
        receiver-facing caches, sharded sender-private controller rows."""
        return EdgeCommState(
            last_sent=rep,
            residual=shard if self.codec.has_residual else None,
            threshold=shard,
            drift_ema=shard,
            ever_delivered=rep)

    def reset_edges(self, state: EdgeCommState, reset,
                    ctx: PodContext = DENSE_CTX) -> EdgeCommState:
        """Per-link state on edges where `reset` [N, E] > 0 returns to its
        init_state values — the defined carry/reset semantics for edges
        whose endpoint churned out and REJOINED: the rejoined device is a
        fresh device, so the link's reconstruction reference, error-feedback
        residual, adaptive threshold/EMA and delivery history all restart
        (the first payload after a reset carries the full model through
        delta codecs again, and `on_silence="stale"` masks the link until
        that redelivery because `ever_delivered` is cleared).  An edge that
        merely DISAPPEARS (dropout / a Gilbert–Elliott burst / a rewiring
        phase) is NOT reset: its state freezes bit-identically — the
        existing failed-link semantics — and transmission resumes against
        the frozen reference when the edge returns.  Zero-`reset` edges are
        left bit-identical."""
        r = reset > 0
        rr = ctx.rows(reset) > 0
        residual = state.residual
        if residual is not None:
            rb = rr.reshape(rr.shape + (1,) * (residual.ndim - 2))
            residual = jnp.where(rb, 0.0, residual)
        return EdgeCommState(
            last_sent=jnp.where(r[:, :, None], 0.0, state.last_sent),
            residual=residual,
            threshold=jnp.where(rr, self.thr0, state.threshold),
            drift_ema=jnp.where(rr, 0.0, state.drift_ema),
            ever_delivered=jnp.where(r, 0.0, state.ever_delivered),
        )

    def _swap_layout(self, arr):
        """Swap a full [N, E, ...] array between the sender and receiver
        edge layouts (an involution: entry (i, e) of the result reads the
        other endpoint's slot for the same directed link, nbr_idx[i, e] at
        rev_slot[i, e]).  Receiver->sender: link_mask[r, e] becomes the
        sender-side ack for i -> nbr_idx[i, e].  Sender->receiver: edge
        state (i, d) lands at the slot where receiver r hears i.  Only
        legal on replicated quantities — the swap crosses rows."""
        return arr[self.nbr_idx, self.rev_slot]

    def recv_layout(self, arr):
        """Receiver-layout view of a full sender-layout [N, E] panel,
        zeroed on padding slots: entry (r, e) is the sender's value for
        the directed link (nbr_idx[r, e] -> r).  Padding slots of the swap
        alias edge (0, 0), so the valid mask is applied here — this is the
        orientation the telemetry channels (repro.obs) observe fired gates
        in, matching the per-node transport's receiver panel and the
        canonical (dst, src) edge order after the panel flatten."""
        return self._swap_layout(arr) * self.nbr_valid

    def _gather_receiver_rows(self, new_last_full, rows):
        """The reverse-slot gather: receiver row r's slot e reads sender
        nbr_idx[r, e]'s reference at slot rev_slot[r, e] out of the full
        per-link table — one XLA row gather over the flattened [N*E, D]
        view (a pure copy; bitwise identical to fancy indexing)."""
        flat_idx = (rows(self.nbr_idx) * self.e + rows(self.rev_slot))
        r = int(flat_idx.shape[0])
        gathered = jnp.take(new_last_full.reshape(self.n * self.e, self.d),
                            flat_idx.reshape(-1), axis=0)
        gathered = self._unflatten(gathered)
        return jax.tree.map(
            lambda l: l.reshape((r, self.e) + l.shape[1:]), gathered)

    def exchange(self, stacked_params, state: EdgeCommState, link_mask,
                 rng=None, live=None, reset=None, *,
                 ctx: PodContext = DENSE_CTX, wire: str = "encoded"):
        """One per-edge transport round for the caller's block of rows.

        Args:
          stacked_params: pytree, leaves [R, ...] — the block's models (all
            N rows on the dense context).
          state: EdgeCommState (replicated caches + the block's controller
            rows).
          link_mask: FULL [N, E] receiver-layout exogenous link mask (1 =
            the (nbr_idx[r, e] -> r) link is up; includes neighbour
            validity and, under a dynamics process, the round's live-edge
            mask).  Always full-axis: the link-layer ack reaches the sender
            through the layout swap, which crosses rows.
          rng: PRNG key when the codec wants one (consumed replicated over
            the full edge set and row-sliced — see GossipTransport).
          live: optional FULL [N, E] {0,1} SYMMETRIC live-edge mask from a
            `repro.dynamics.GraphProcess` (symmetry makes the sender and
            receiver layouts coincide).  A dead edge does not exist this
            round: its sender cannot fire on it (no drift gate, no bytes)
            and its adaptive threshold/EMA freeze — unlike a `link_mask`
            failure, which is a LOSS the sender pays for.
          reset: optional FULL [N, E] {0,1} edges whose per-link state
            returns to bootstrap BEFORE this round's drift is measured (see
            reset_edges; the engine raises it on every edge incident to a
            node that rejoined after churn).
          ctx: the block's PodContext (see module docstring).
          wire: "encoded" gathers the codec payload, "decoded" the
            reconstructions — bit-identical, see GossipTransport.exchange.

        Returns (gathered, agg_mask, gate_full, new_state):
          gathered — pytree with leaves [R, E, ...]: slot e of block row r
            holds r's CURRENT reconstruction of neighbour nbr_idx[r, e]
            (fresh if the edge delivered this round, the per-link stale
            cache otherwise — receivers always have their own cache),
          agg_mask — [R, E] receiver-layout aggregation mask per the
            on_silence policy,
          gate_full — [N, E] sender-layout {0,1} fired edges, replicated
            (bytes accounting),
          new_state — the threaded EdgeCommState.
        """
        _check_wire(wire)
        codec, cfg = self.codec, self.config
        rows = ctx.rows
        w, _ = tree_flatten_stacked(stacked_params)
        r = int(w.shape[0])
        if reset is not None:
            state = self.reset_edges(state, reset, ctx=ctx)
        # a dynamics-dead edge is excluded from validity for the round:
        # no gate, no bytes, frozen controller state.
        valid_full = (self.nbr_valid if live is None
                      else self.nbr_valid * live)
        last_full = state.last_sent
        last = rows(last_full)
        gate, drift = edge_drift_gate(w, last, state.threshold,
                                      rows(valid_full))
        # link-layer ack: a payload advances its edge's state only if the
        # edge fired AND the link stayed up (sender layout; the swap crosses
        # rows, so it runs on the replicated full mask).
        sender_link_full = self._swap_layout(link_mask)
        delivered = gate * rows(sender_link_full)

        x = (w[:, None, :] - last if codec.is_delta
             else jnp.broadcast_to(w[:, None, :], last.shape))
        if self.wants_rng:
            if rng is None:
                raise ValueError(f"codec {codec.name!r} needs an rng key")
            # one key per CANONICAL directed edge (CSR id), not per padded
            # slot — the sparse per-edge transport indexes the same split,
            # so the two layouts' stochastic codecs agree bit-for-bit.
            keys = rows(jax.random.split(
                rng, max(self.num_directed, 1))[self.edge_id])
        else:
            keys = jnp.zeros((r, self.e, 2), jnp.uint32)

        def enc(xi, key, res):
            return codec.encode(xi, rng=key if self.wants_rng else None,
                                residual=res)

        vv = lambda f: jax.vmap(jax.vmap(f))  # noqa: E731
        if codec.has_residual:
            payload, enc_res = vv(enc)(x, keys, state.residual)
        else:
            payload, _ = vv(lambda xi, key: enc(xi, key, None))(x, keys)
            enc_res = None

        def dec(p):
            return codec.decode(p, out_size=self.d)

        if wire == "encoded":
            dec_full = vv(dec)(jax.tree.map(ctx.gather, payload))
        else:
            dec_full = ctx.gather(vv(dec)(payload))
        gate_full = ctx.gather(gate)
        delivered_full = gate_full * sender_link_full

        recon = last_full + dec_full if codec.is_delta else dec_full
        new_last = jnp.where(delivered_full[:, :, None] > 0, recon, last_full)
        if codec.has_residual:
            # the EF residual tracks DELIVERED information only: a dropped
            # or silent link keeps its residual bit-identical (the pending
            # drift is recomputed from the unchanged reference next round).
            keep = delivered.reshape(
                (r, self.e) + (1,) * (enc_res.ndim - 2)) > 0
            new_res = jnp.where(keep, enc_res, state.residual)
        else:
            new_res = None

        if cfg.policy == "adaptive":
            new_thr, new_ema = adaptive_threshold_update(
                state.threshold, state.drift_ema, drift, gate,
                rows(valid_full), target=cfg.target_trigger,
                ema_beta=cfg.drift_ema_beta, rate=cfg.threshold_rate)
        else:
            new_thr, new_ema = state.threshold, state.drift_ema
        ever = jnp.maximum(state.ever_delivered, delivered_full)
        new_state = EdgeCommState(last_sent=new_last, residual=new_res,
                                  threshold=new_thr, drift_ema=new_ema,
                                  ever_delivered=ever)

        # receiver view: slot e of block row r is sender j's edge state
        # toward r — the reverse-slot gather out of the replicated table.
        gathered = self._gather_receiver_rows(new_last, rows)
        if cfg.on_silence == "drop":
            agg_mask = rows(link_mask * self._swap_layout(gate_full))
        else:
            # stale: aggregate the per-link cache at full weight, masking
            # only links that never delivered (cache = zero bootstrap);
            # exogenous failures still drop (a loss, not a decision).
            agg_mask = rows(link_mask * self._swap_layout(ever))
        return gathered, agg_mask, gate_full, new_state


class SparseEdgeCommState(NamedTuple):
    """Per-edge transport state in the flat [E] CSR edge-list layout.

    Entry e is the directed link ``edge_src[e] -> edge_dst[e]`` of a
    :class:`~repro.graphs.sparse.SparseTopology` — the dense layout's
    `[N, max_deg]` panels with the padding removed.  All fields are
    replicated over pods: the edge axis does not tile the node-axis pod
    mesh (per-pod edge BANKS over the graph cut are the halo-exchange
    follow-up tracked in ROADMAP.md)."""

    last_sent: jnp.ndarray            # [E, D] per-link reconstruction ref
    residual: Optional[jnp.ndarray]   # [E, ...] per-link EF residual
    threshold: jnp.ndarray            # [E] per-link trigger thresholds
    drift_ema: jnp.ndarray            # [E] per-link drift EMA (adaptive)
    ever_delivered: jnp.ndarray       # [E] {0,1}: link ever delivered?


class SparseEdgeGossipTransport:
    """Per-edge transport over a flat CSR edge list — no layout swap at all.

    The dense :class:`EdgeGossipTransport` keys state by (sender, slot) and
    needs TWO index gymnastics per round: the `rev_slot` layout swap (sender
    acks from the receiver-layout link mask) and the reverse-slot gather
    (receivers read each sender's per-link reference).  In the CSR edge
    list, a directed edge id is simultaneously the sender-layout AND the
    receiver-layout address of the same link: the gate, the delivery, the
    aggregation mask and the reconstruction reference of edge e all live at
    position e, and receiver i's delivered neighbour models are exactly
    `last_sent[row_offsets[i]:row_offsets[i+1]]` — the CSR row the
    SparseNeighborhood buckets already enumerate (`WidthBucket.epos`).
    `rev_edge` (the permutation pairing e with its opposite direction) is
    kept for state introspection — e.g. asserting a churn reset cleared
    BOTH directed records of a link — not for the data path.

    Bit-parity with the dense twin is by construction: the per-edge drift
    gate, the Robbins-Monro controller and the codec are the same
    elementwise programs, the rng stream is keyed by the same canonical CSR
    edge id, and every mask composition is a product of exact {0,1} floats.

    The model rows are the only cross-pod movement (`ctx.gather` of the
    [R, D] block); encode/decode then runs replicated over the full edge
    axis, so the `wire` choice does not change what crosses pods here —
    accepted for signature parity with the dense transport."""

    def __init__(self, config: CommConfig, stacked_params, st):
        from repro.graphs.sparse import rev_edge_permutation

        self.config = config
        self.codec = config.make_codec()
        mat, self._unflatten = tree_flatten_stacked(stacked_params)
        self.n, self.d = int(mat.shape[0]), int(mat.shape[1])
        self.e_dir = int(st.num_directed)
        self.payload_bytes = self.codec.payload_bytes_for(self.d)
        self.dense_bytes = 4 * self.d
        self.wants_rng = (self.codec.needs_rng
                          and getattr(self.codec, "stochastic", True))
        self.edge_src = jnp.asarray(st.edge_src.astype(np.int32))
        self.edge_dst = jnp.asarray(st.edge_dst.astype(np.int32))
        self.rev_edge = jnp.asarray(rev_edge_permutation(st))
        self.num_edges = float(self.e_dir)  # directed edge count
        # shared (re)start threshold — see EdgeGossipTransport.thr0
        self.thr0 = (config.trigger_threshold if config.policy == "fixed"
                     else 0.0)

    def init_state(self, stacked_params) -> SparseEdgeCommState:
        mat, _ = tree_flatten_stacked(stacked_params)
        if self.codec.has_residual:
            res0 = self.codec.init_residual(mat[0])
            residual = jnp.zeros((self.e_dir,) + res0.shape, jnp.float32)
        else:
            residual = None
        return SparseEdgeCommState(
            last_sent=jnp.zeros((self.e_dir, self.d), jnp.float32),
            residual=residual,
            threshold=jnp.full((self.e_dir,), self.thr0, jnp.float32),
            drift_ema=jnp.zeros((self.e_dir,), jnp.float32),
            ever_delivered=jnp.zeros((self.e_dir,), jnp.float32),
        )

    def state_specs(self, shard, rep) -> SparseEdgeCommState:
        """All replicated: the edge axis does not tile the node-axis pod
        mesh, and every pod recomputes the full-edge update from the
        gathered model rows deterministically (so replicas cannot
        diverge).  Sharding the edge bank by pod-incident cut is the
        halo-exchange follow-up in ROADMAP.md."""
        del shard
        return SparseEdgeCommState(
            last_sent=rep,
            residual=rep if self.codec.has_residual else None,
            threshold=rep, drift_ema=rep, ever_delivered=rep)

    def reset_edges(self, state: SparseEdgeCommState, reset,
                    ctx: PodContext = DENSE_CTX) -> SparseEdgeCommState:
        """Edges where `reset` [E] > 0 return to their init_state values —
        the same rejoin semantics as EdgeGossipTransport.reset_edges
        (reference, residual, threshold/EMA and delivery history restart;
        zero-`reset` edges stay bit-identical).  The engine raises reset on
        BOTH directed records of every link incident to a rejoined node
        (`max(rejoined[edge_src], rejoined[edge_dst])` is symmetric under
        `rev_edge` by construction)."""
        del ctx  # state is replicated; kept for signature parity
        r = reset > 0
        residual = state.residual
        if residual is not None:
            rb = r.reshape(r.shape + (1,) * (residual.ndim - 1))
            residual = jnp.where(rb, 0.0, residual)
        return SparseEdgeCommState(
            last_sent=jnp.where(r[:, None], 0.0, state.last_sent),
            residual=residual,
            threshold=jnp.where(r, self.thr0, state.threshold),
            drift_ema=jnp.where(r, 0.0, state.drift_ema),
            ever_delivered=jnp.where(r, 0.0, state.ever_delivered),
        )

    def exchange(self, stacked_params, state: SparseEdgeCommState, link_mask,
                 rng=None, live=None, reset=None, *,
                 ctx: PodContext = DENSE_CTX, wire: str = "encoded"):
        """One per-edge transport round over the flat edge list.

        Args:
          stacked_params: pytree, leaves [R, ...] — the block's models (all
            N rows on the dense context).
          state: SparseEdgeCommState (replicated).
          link_mask: [E] {0,1} exogenous per-directed-edge link mask (the
            engine folds participation draws and, under dynamics, the live
            mask into it).
          rng: PRNG key when the codec wants one — split over the canonical
            directed edge ids, the SAME stream the dense per-edge transport
            indexes through its slot panel.
          live: optional [E] {0,1} live-edge mask from a GraphProcess: a
            dead edge does not exist this round (no gate, no bytes, frozen
            controller state), unlike a `link_mask` failure the sender pays
            for.
          reset: optional [E] {0,1} — edges rebooted BEFORE this round's
            drift is measured (see reset_edges).
          ctx / wire: see class docstring.

        Returns (edge_table, agg_mask, gate, new_state):
          edge_table — [E, D] fp32: entry e is what edge e's receiver
            currently holds for its sender (fresh if delivered this round,
            the per-link stale cache otherwise).  Feed it to
            SparseNeighborhood(edge_table=...) — receiver rows address it
            by CSR edge position, no gather needed.
          agg_mask — [E] receiver aggregation mask per on_silence,
          gate — [E] {0,1} fired edges (bytes accounting),
          new_state — the threaded SparseEdgeCommState.
        """
        _check_wire(wire)
        codec, cfg = self.codec, self.config
        w, _ = tree_flatten_stacked(stacked_params)
        w_full = ctx.gather(w)  # [N, D] — the only cross-pod movement
        if reset is not None:
            state = self.reset_edges(state, reset, ctx=ctx)
        valid = (jnp.ones((self.e_dir,), jnp.float32) if live is None
                 else live)
        last = state.last_sent
        w_edge = w_full[self.edge_src]  # [E, D] each edge's sender row
        # the same elementwise gate as the dense layout, on [E, 1] panels
        g2, d2 = edge_drift_gate(w_edge, last[:, None, :],
                                 state.threshold[:, None], valid[:, None])
        gate, drift = g2[:, 0], d2[:, 0]
        # link-layer ack — the edge id IS the sender-layout address, so the
        # dense path's rev_slot swap is the identity here.
        delivered = gate * link_mask

        x = w_edge - last if codec.is_delta else w_edge
        if self.wants_rng:
            if rng is None:
                raise ValueError(f"codec {codec.name!r} needs an rng key")
            keys = jax.random.split(rng, max(self.e_dir, 1))
        else:
            keys = jnp.zeros((self.e_dir, 2), jnp.uint32)

        def enc(xi, key, res):
            return codec.encode(xi, rng=key if self.wants_rng else None,
                                residual=res)

        if codec.has_residual:
            payload, enc_res = jax.vmap(enc)(x, keys, state.residual)
        else:
            payload, _ = jax.vmap(lambda xi, key: enc(xi, key, None))(x, keys)
            enc_res = None

        dec_all = jax.vmap(lambda p: codec.decode(p, out_size=self.d))(payload)
        recon = last + dec_all if codec.is_delta else dec_all
        new_last = jnp.where(delivered[:, None] > 0, recon, last)
        if codec.has_residual:
            # EF residual tracks DELIVERED information only (see the dense
            # twin): dropped/silent links keep their residual bit-identical.
            keep = delivered.reshape(
                (self.e_dir,) + (1,) * (enc_res.ndim - 1)) > 0
            new_res = jnp.where(keep, enc_res, state.residual)
        else:
            new_res = None

        if cfg.policy == "adaptive":
            new_thr, new_ema = adaptive_threshold_update(
                state.threshold, state.drift_ema, drift, gate, valid,
                target=cfg.target_trigger, ema_beta=cfg.drift_ema_beta,
                rate=cfg.threshold_rate)
        else:
            new_thr, new_ema = state.threshold, state.drift_ema
        ever = jnp.maximum(state.ever_delivered, delivered)
        new_state = SparseEdgeCommState(
            last_sent=new_last, residual=new_res, threshold=new_thr,
            drift_ema=new_ema, ever_delivered=ever)

        if cfg.on_silence == "drop":
            agg_mask = link_mask * gate
        else:
            agg_mask = link_mask * ever
        return new_last, agg_mask, gate, new_state


def codec_roundtrip_stacked(codec: Codec, stacked, rng=None):
    """Reference-free encode->decode of stacked [N, ...] models.

    The dist-layer rounds (repro.dist.dfl_step) use this to model wire
    effects without transport state: delta codecs compress against the
    implicit zero reference (= the full model goes through the codec).
    Returns the decoded stacked pytree (leaves cast back to input dtypes).
    """
    w, unflatten = tree_flatten_stacked(stacked)
    n, d = int(w.shape[0]), int(w.shape[1])
    wants_rng = codec.needs_rng and getattr(codec, "stochastic", True) \
        and rng is not None
    keys = (jax.random.split(rng, n) if wants_rng
            else jnp.zeros((n, 2), jnp.uint32))

    def enc_dec(xi, key):
        payload, _ = codec.encode(xi, rng=key if wants_rng else None)
        return codec.decode(payload, out_size=d)

    return unflatten(jax.vmap(enc_dec)(w, keys))
