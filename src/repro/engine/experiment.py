"""`Experiment`: the single front door for decentralized-learning runs.

    Experiment(world, method, comm=..., backend=..., schedule=...).run()

packages the paper's whole experimental procedure — heterogeneous per-node
init, B local SGD(momentum) steps, neighbour exchange (optionally through
the repro.comm gossip transport), method aggregation, periodic evaluation —
behind one object:

  * `world`    — the physical problem: model, topology, per-node datasets,
    test set (:class:`World`, or `World.synthetic(...)` for the paper's
    synthetic setups), optionally with a `repro.dynamics.GraphProcess`
    making the topology time-varying (edge dropout, bursty links, churn,
    rewiring — see docs/dynamics.md);
  * `method`   — a name in the strategy registry (`available_methods()`;
    plug in your own with `register_method`);
  * `comm`     — optional `repro.comm.CommConfig`: codecs, event triggers,
    per-edge state, exact bytes-on-wire accounting.  The per-node or
    per-edge transport is selected from the config and the strategy's
    declared :class:`~repro.engine.Capabilities` — never by caller
    branching — and every transport runs on every backend;
  * `backend`  — "vmap" (one jitted program over the stacked node axis) or
    "shard_map" (the same program over the "pod" mesh axis, one block of
    nodes per pod; bit-identical to vmap, see engine.backends);
  * `wire`     — what the shard_map exchange gathers: "encoded" (default —
    the codec payload crosses the pod interconnect; every pod decodes the
    same bytes) or "decoded" (the reconstructed fp32 rows — the small-N
    oracle).  Bit-identical by construction; a no-op under vmap;
  * `schedule` — rounds / eval cadence / execution mode: "fused" compiles
    the WHOLE schedule (K rounds + gated evals) into one `lax.scan` program
    dispatched once, "loop" dispatches one XLA call per round (the legacy
    behaviour; same math bit-for-bit, see BENCH_engine.json for the
    rounds/sec gap).

Mutable run state (params, optimizer and transport state, rng, byte
accounting) lives on the instance so `run()` can be called repeatedly and
metrics continue where the last call stopped.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time as _time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import (WIRES, CommConfig, EdgeGossipTransport,
                        GossipTransport, SparseEdgeGossipTransport)
from repro.core.virtual_teacher import make_loss_fn
from repro.data.allocation import stride_ordered_shards
from repro.data.pipeline import Batcher
from repro.dist.sharding import NODE_AXIS, auto_mesh, make_mesh
from repro.dynamics import GraphProcess
from repro.engine import backends
from repro.engine.neighborhood import build_sparse_plan
from repro.engine.strategies import MethodSpec, get_method
from repro.fl.metrics import RoundMetrics
from repro.fl.trainer import make_eval_fn, make_grad_fn, make_train_step
from repro.graphs.sparse import SparseTopology
from repro.graphs.topology import Topology
from repro.models.api import SmallModel
from repro.obs import (RunLedger, Telemetry, log_round, round_record,
                       run_manifest, spans)
from repro.optim.sgd import sgd_momentum
from repro.timing import Timing
from repro.utils.pytree import tree_flatten_stacked

SCHEDULE_MODES = ("fused", "loop")
LAYOUTS = ("dense", "sparse")


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Local-training and aggregation hyper-parameters (Alg. 1 knobs)."""

    steps_per_round: int = 4   # B in Alg. 1 (minibatch steps between exchanges)
    batch_size: int = 32
    lr: float = 1e-3
    momentum: float = 0.9
    beta: float = 0.95         # VT confidence (Eq. 7)
    s: float = 1.0             # DecDiff damping (Eq. 5)
    participation: float = 1.0  # per-neighbour delivery probability per round
    seed: int = 0
    eval_batch: int = 128
    ge_lr: Optional[float] = None  # CFA-GE gradient-apply LR (default: lr)
    # Heterogeneous local training (paper Alg. 1: E "is not necessarily the
    # same at all nodes"): per-node number of local steps per round, sampled
    # uniformly from [min, steps_per_round].  0 disables (= homogeneous).
    hetero_steps_min: int = 0


@dataclasses.dataclass(frozen=True)
class Schedule:
    """How many rounds, how often to eval, and how the rounds execute.

    `deadline` (simulated seconds; requires `World(timing=...)`) turns each
    round into an event-clock DEADLINE TICK: a node trains as many local
    steps as fit in the deadline (capped at `steps_per_round` — stragglers
    train fewer), and a payload is aggregated only if `send_time + latency
    + bytes/bandwidth <= deadline`; late arrivals fall into the existing
    stale/drop silence paths.  `deadline=None` keeps the schedule
    synchronous — every round waits for the slowest node and link and the
    clock merely reports the makespan.  See docs/timing.md."""

    rounds: int = 100
    eval_every: int = 5
    mode: str = "fused"  # "fused" (one lax.scan program) | "loop" (per-round)
    deadline: Optional[float] = None  # simulated seconds per round tick

    def __post_init__(self):
        if self.mode not in SCHEDULE_MODES:
            raise ValueError(f"schedule mode must be one of {SCHEDULE_MODES}, "
                             f"got {self.mode!r}")
        if self.deadline is not None and not self.deadline > 0:
            raise ValueError(f"deadline must be > 0 simulated seconds, "
                             f"got {self.deadline}")

    @staticmethod
    def eval_rounds(rounds: int, eval_every: int):
        """The eval cadence (the single source both schedule modes use):
        after round 0, every `eval_every` rounds, and after the last
        round."""
        return [r for r in range(rounds)
                if r % eval_every == 0 or r == rounds - 1]


@dataclasses.dataclass
class World:
    """The physical problem: who talks to whom, over what data.

    `topo` is either a dense :class:`~repro.graphs.Topology` (padded
    [N, max_deg] layout, the small-N default) or a
    :class:`~repro.graphs.SparseTopology` (CSR edge list — the 10^4+-node
    layout; `Experiment` selects the matching engine automatically, see
    `Experiment(layout=...)`).

    `dynamics` optionally makes "who talks to whom" time-varying: a
    :class:`repro.dynamics.GraphProcess` (edge dropout, Gilbert–Elliott
    bursty links, node churn, periodic rewiring, …) that realizes a
    per-round live-edge mask over the topology — `topo` then describes the
    POSSIBLE links and the process decides which exist each round.  See
    docs/dynamics.md."""

    model: SmallModel
    topo: "Topology | SparseTopology"
    xs: List[np.ndarray]       # per-node train inputs
    ys: List[np.ndarray]       # per-node train labels
    x_test: np.ndarray
    y_test: np.ndarray
    dynamics: Optional[GraphProcess] = None
    # Optional event clock (repro.timing): per-node step times and per-edge
    # latency/bandwidth pricing each round in simulated seconds.  With
    # `Schedule(deadline=...)` the rounds become deadline ticks (stragglers
    # train fewer steps, late payloads miss the round); without one the
    # schedule stays synchronous and the clock reports the makespan.
    timing: Optional[Timing] = None
    # Optional telemetry (repro.obs): opt-in per-node/per-edge channel
    # accumulators riding the scan carry (consensus/drift probes, exact
    # per-edge bytes, staleness ages, ...), a schema-validated JSONL run
    # ledger, and Chrome-trace export of the event clock.  `telemetry=None`
    # is bit-identical to an engine without the subsystem.  See
    # docs/observability.md.
    telemetry: Optional[Telemetry] = None

    @classmethod
    def synthetic(cls, dataset: str = "synth-mnist", nodes: int = 16,
                  topology: str = "erdos_renyi", seed: int = 0,
                  scale: float = 0.05, min_per_class: int = 1,
                  model: Optional[SmallModel] = None,
                  dynamics: Optional[GraphProcess] = None,
                  timing: Optional[Timing] = None,
                  telemetry: Optional[Telemetry] = None, **topo_kwargs):
        """The paper's synthetic worlds in one call: seeded dataset,
        complex-network topology (extra kwargs go to the graph builder,
        e.g. p=0.25 for ER, m=2 for BA), truncated-Zipf non-IID split."""
        import inspect

        from repro.data import make_dataset, zipf_allocation
        from repro.data.allocation import split_by_allocation
        from repro.graphs import make_topology
        from repro.graphs.topology import TOPOLOGY_BUILDERS
        from repro.models.mlp_cnn import model_for_dataset

        ds = make_dataset(dataset, seed=seed, scale=scale)
        builder = TOPOLOGY_BUILDERS.get(topology)
        if builder is not None and \
                "seed" in inspect.signature(builder).parameters:
            topo_kwargs.setdefault("seed", seed)
        topo = make_topology(topology, n=nodes, **topo_kwargs)
        alloc = zipf_allocation(ds.y_train, nodes, seed=seed,
                                min_per_class=min_per_class)
        xs, ys = split_by_allocation(ds.x_train, ds.y_train, alloc)
        model = model or model_for_dataset(dataset, ds.num_classes)
        return cls(model=model, topo=topo, xs=xs, ys=ys,
                   x_test=ds.x_test, y_test=ds.y_test, dynamics=dynamics,
                   timing=timing, telemetry=telemetry)


def _default_mesh(n: int):
    """A pure pod mesh over ALL local devices (1 pod on a single-device
    host — the shard_map lowering then still runs, just without an actual
    exchange axis split).  Raises rather than leave devices idle when the
    node count does not tile them; pass `mesh=` to use fewer."""
    d = len(jax.devices())
    if n % d:
        raise ValueError(
            f"{n} DFL nodes do not tile the {d} local devices; pass mesh= "
            f"with a pod count that divides {n}")
    return make_mesh((d,), (NODE_AXIS,))


class Experiment:
    """One method over one world — see module docstring."""

    def __init__(self, world: World, method: str = "decdiff+vt", *,
                 comm: Optional[CommConfig] = None, backend: str = "vmap",
                 wire: str = "encoded",
                 schedule: Optional[Schedule] = None,
                 train: Optional[TrainConfig] = None, mesh=None,
                 layout: Optional[str] = None, **train_overrides):
        if backend not in backends.BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"available: {backends.BACKENDS}")
        if wire not in WIRES:
            raise ValueError(f"unknown wire {wire!r}; available: {WIRES}")
        if layout is not None and layout not in LAYOUTS:
            raise ValueError(f"unknown layout {layout!r}; "
                             f"available: {LAYOUTS}")
        self.wire = wire
        self.method: MethodSpec = get_method(method)
        self.strategy = self.method.strategy
        self.world = world
        self.backend = backend
        self.schedule = schedule or Schedule()
        train = train or TrainConfig()
        if train_overrides:
            train = dataclasses.replace(train, **train_overrides)
        self.train = train

        model, topo = world.model, world.topo
        if not (topo.num_nodes == len(world.xs) == len(world.ys)):
            raise ValueError(
                f"world has {topo.num_nodes} nodes but "
                f"{len(world.xs)}/{len(world.ys)} data shards")
        # --- node-axis layout: dense padded [N, max_deg] (the small-N
        # oracle) or sparse CSR edge list (the 10^4+-node engine).  The
        # layout follows the topology type unless overridden — dense over a
        # SparseTopology densifies it (guarded ≤4096 nodes, the oracle
        # regime); sparse over a Topology converts it, so the same world
        # can run both for equivalence pins.
        if layout is None:
            layout = "sparse" if isinstance(topo, SparseTopology) else "dense"
        self.layout = layout
        # Layout support is capability-driven: the strategy's Capabilities
        # record declares which node-axis layouts it lowers to, plus ONE
        # derived restriction — a gossip strategy without a flat_aggregate
        # form only has the padded-gather lowering, which is dense-only.
        caps = self.strategy.capabilities
        allowed = tuple(
            lo for lo in caps.layouts
            if not (lo == "sparse" and caps.kind == "gossip"
                    and self.strategy.flat_aggregate is None))
        if layout not in allowed:
            why = ("declares no flat_aggregate form, so only the dense "
                   "padded-gather lowering exists"
                   if layout in caps.layouts else
                   "declares it unsupported in its Capabilities record")
            raise ValueError(
                f"method {method!r}: strategy "
                f"{type(self.strategy).__name__} {why}; supported layouts: "
                f"{allowed}")
        if layout == "dense" and isinstance(topo, SparseTopology):
            topo = topo.to_topology()
        elif layout == "sparse" and not isinstance(topo, SparseTopology):
            topo = SparseTopology.from_topology(topo)
        # --- dynamics (repro.dynamics): bind the graph process once; it may
        # augment the static layout (rewiring compiles against the family's
        # union graph), so everything below derives from the bound topo.
        self.dynamics = world.dynamics
        self.bound_dyn = None
        if world.dynamics is not None:
            if not isinstance(world.dynamics, GraphProcess):
                raise TypeError(
                    f"World.dynamics must be a repro.dynamics.GraphProcess, "
                    f"got {type(world.dynamics).__name__}")
            self.bound_dyn = world.dynamics.bind(topo)
            topo = self.bound_dyn.topo
        self.model = model
        self.topo = topo
        self.n = topo.num_nodes
        self.mesh = (auto_mesh(mesh) if mesh is not None else
                     _default_mesh(self.n) if backend == "shard_map" else None)

        # One copy of the training shards on the device, rows in minibatch
        # order: each local step reads one contiguous window a node.
        self.batcher = Batcher(batch_size=train.batch_size,
                               sample_shape=world.xs[0].shape[1:])
        x_shards, y_shards, counts = stride_ordered_shards(
            world.xs, world.ys, self.batcher.batch_size, self.batcher.stride)
        self.x_shards = jnp.asarray(x_shards)
        self.y_shards = jnp.asarray(y_shards.astype(np.int32))
        self.counts = jnp.asarray(counts.astype(np.int32))
        self.x_test = jnp.asarray(world.x_test)
        self.y_test = jnp.asarray(world.y_test.astype(np.int32))

        # --- graph tensors (padded dense layout OR the sparse plan) ---
        if self.layout == "sparse":
            n_pods = 1
            if backend == "shard_map" and self.mesh is not None:
                n_pods = int(dict(self.mesh.shape).get(NODE_AXIS, 1))
            self.nbr_idx = None
            self.nbr_valid = None
            self.nbr_weight = None
            self.sparse_plan = build_sparse_plan(topo, counts, n_pods)
        else:
            self.sparse_plan = None
            idx = topo.neighbor_idx.astype(np.int32)
            self.nbr_idx = jnp.asarray(np.maximum(idx, 0))
            self.nbr_valid = jnp.asarray(
                topo.neighbor_mask.astype(np.float32))
            # combined ω_ij * |D_j| weights (aggregators normalize
            # internally, which realizes p_ij = |D_j| / Σ_{N_i} |D_j| of
            # Eqs. 4/6/9).
            omega = topo.neighbor_weights()  # [N, D]
            dj = counts[np.maximum(idx, 0)].astype(np.float32)
            self.nbr_weight = jnp.asarray(omega * dj * topo.neighbor_mask)

        self.optimizer = sgd_momentum(lr=train.lr, momentum=train.momentum)
        self.loss_fn = make_loss_fn(self.method.loss, beta=train.beta)
        self._train_step = make_train_step(self.model, self.optimizer,
                                           self.loss_fn)
        self._grad_fn = make_grad_fn(self.model, self.loss_fn)
        self._eval_raw = jax.vmap(
            make_eval_fn(self.model,
                         batch_size=min(train.eval_batch, len(world.x_test))),
            in_axes=(0, None, None),
        )
        self._eval = jax.jit(jax.named_scope(spans.EVAL)(self._eval_raw))

        # --- init (heterogeneous unless the method coordinates) ---
        base = jax.random.PRNGKey(train.seed)
        if self.method.common_init:
            keys = jnp.broadcast_to(jax.random.PRNGKey(train.seed + 1),
                                    (self.n, 2))
        else:
            keys = jax.random.split(jax.random.fold_in(base, 17), self.n)
        self.params = jax.vmap(self.model.init)(keys)
        self.opt_state = jax.vmap(self.optimizer.init)(self.params)
        self.rng = jax.random.fold_in(base, 23)

        # --- gossip transport (capability-gated; repro.comm) ---
        self.comm = comm
        self.transport = None
        self.comm_state = None
        self.comm_bytes_total = 0.0
        self._trig_sum = 0.0
        self._comm_rounds = 0
        self.trig_history: List[float] = []  # per-round triggered fraction
        if comm is not None:
            if not self.strategy.capabilities.transport:
                from repro.engine.strategies import _REGISTRY
                roster = sorted(m for m, s in _REGISTRY.items()
                                if s.strategy.capabilities.transport)
                raise ValueError(
                    f"comm transport models neighbour model-gossip only; "
                    f"method {method!r} is unsupported "
                    f"(transport-capable methods: {roster})")
            if comm.use_per_edge:
                if self.layout == "sparse":
                    self.transport = SparseEdgeGossipTransport(
                        comm, self.params, topo)
                else:
                    self.transport = EdgeGossipTransport(
                        comm, self.params, topo.neighbor_idx,
                        topo.neighbor_mask)
            elif self.layout == "sparse":
                self.transport = GossipTransport(
                    comm, self.params,
                    edge_src=topo.edge_src, edge_dst=topo.edge_dst)
            else:
                self.transport = GossipTransport(
                    comm, self.params, nbr_idx=topo.neighbor_idx,
                    nbr_valid=topo.neighbor_mask)
            self.comm_state = self.transport.init_state(self.params)

        # --- dynamics state + live-edge accounting ---
        self.dyn_state = (self.bound_dyn.state0
                          if self.bound_dyn is not None else None)
        self._total_directed = (float(topo.num_directed)
                                if self.layout == "sparse"
                                else float(topo.neighbor_mask.sum()))
        self._live_sum = 0.0
        self._live_rounds = 0
        self.live_history: List[float] = []  # per-round live-edge fraction

        # --- event clock (repro.timing): bind the time models once, priced
        # from the transport's EXACT bytes-on-wire (dense fp32 model size
        # without one) ---
        self.timing = world.timing
        self.bound_timing = None
        self.time_state = None
        self.deadline = self.schedule.deadline
        if world.timing is not None:
            if not isinstance(world.timing, Timing):
                raise TypeError(
                    f"World.timing must be a repro.timing.Timing, "
                    f"got {type(world.timing).__name__}")
            if self.transport is not None:
                payload = float(self.transport.payload_bytes)
            else:
                flat, _ = tree_flatten_stacked(self.params)
                payload = 4.0 * float(flat.shape[1])
            self.bound_timing = world.timing.bind(topo, payload)
            self.time_state = self.bound_timing.state0
        elif self.deadline is not None:
            raise ValueError(
                "Schedule(deadline=...) prices rounds in simulated seconds "
                "and needs World(timing=...) to define them")
        if (self.bound_dyn is not None and self.bound_dyn.observes
                and self.bound_timing is None):
            raise ValueError(
                f"dynamics process {self.bound_dyn.name!r} observes the "
                f"event clock's per-node compute cost; give the world a "
                f"repro.timing.Timing (World(timing=...))")
        self.sim_time = 0.0
        self.sim_time_history: List[float] = []  # absolute seconds per round
        self._arrived_sum = 0.0
        self._arrived_rounds = 0
        self.arrived_history: List[float] = []  # per-round arrived fraction

        # --- telemetry (repro.obs): bind the channel selection once; the
        # accumulator dict becomes one more scan-carried state and the
        # per-round snapshots one more extras group.  The ledger (when
        # configured) opens here with the run manifest.
        self.telemetry = world.telemetry
        self.bound_obs = None
        self.obs_state = None
        # layout-native channel snapshots, one per round (ALL rounds, not
        # just eval rounds — the trace exporter diffs the cumulative
        # channels round by round)
        self.obs_history: List[Dict] = []
        self.ledger = None
        if world.telemetry is not None:
            if not isinstance(world.telemetry, Telemetry):
                raise TypeError(
                    f"World.telemetry must be a repro.obs.Telemetry, "
                    f"got {type(world.telemetry).__name__}")
            self.bound_obs = world.telemetry.bind(self)
            if self.bound_obs is not None:
                self.obs_state = self.bound_obs.state0
            if world.telemetry.ledger is not None:
                self.ledger = RunLedger(world.telemetry.ledger)
                self.ledger.write_manifest(run_manifest(self))
        # the params-reading probes (consensus/drift) are instantaneous
        # norms consumed only at eval rounds, so they run under the SAME
        # gate as the eval itself: the fused program inlines `_probes_raw`
        # in its static-flag cond, loop mode calls the jitted version at
        # eval rounds — non-eval rounds never pay the flatten + norms.
        self._probes_raw = self._probes = None
        if self.bound_obs is not None and self.bound_obs.has_probes:
            _tele = self.bound_obs

            def _probes_raw(params):
                return _tele.eval_probes(tree_flatten_stacked(params)[0])

            self._probes_raw = _probes_raw
            self._probes = jax.jit(_probes_raw)

        # --- method state + the lowered round ---
        self.agg_state = self.strategy.init_state(self)
        self._round_raw = backends.build_round(self)
        # donate the round-carried state: params, opt, then
        # comm/dyn/time/obs
        donate = tuple(range(2 + sum(self._state_flags())))
        self._round = jax.jit(self._round_raw, donate_argnums=donate)
        self._fused_cache = {}
        # what the compile counters moved in the last compile()
        self.compile_stats: Optional[Dict[str, float]] = None

    # ------------------------------------------------------------------
    def evaluate(self) -> RoundMetrics:
        acc, loss = self._eval(self.params, self.x_test, self.y_test)
        return RoundMetrics(round=-1, acc_per_node=np.asarray(acc),
                            loss_per_node=np.asarray(loss))

    # ------------------------------------------------------------------
    # The generic round calling convention (shared with engine.backends):
    #   round_fn(params, opt, *states, round_idx, rng)
    #     -> (params, opt, *states, rng, loss, *extras)
    # with `states` the present members of (comm_state, dyn_state,
    # time_state, obs_state) in that order and `extras` the present groups
    # of (sent, trig | live | sim_t, arrived | obs_snapshot).  Both
    # schedule modes and the fused scan body unpack by the same four flags.
    def _state_flags(self):
        return (self.transport is not None, self.bound_dyn is not None,
                self.bound_timing is not None, self.bound_obs is not None)

    def _get_states(self):
        has_comm, has_dyn, has_time, has_obs = self._state_flags()
        states = ()
        states += (self.comm_state,) if has_comm else ()
        states += (self.dyn_state,) if has_dyn else ()
        states += (self.time_state,) if has_time else ()
        states += (self.obs_state,) if has_obs else ()
        return states

    def _set_states(self, states):
        has_comm, has_dyn, has_time, has_obs = self._state_flags()
        states = list(states)
        if has_comm:
            self.comm_state = states.pop(0)
        if has_dyn:
            self.dyn_state = states.pop(0)
        if has_time:
            self.time_state = states.pop(0)
        if has_obs:
            self.obs_state = states.pop(0)
        assert not states

    def _fused_program(self, rounds: int, eval_every: int):
        """One jitted program for the whole schedule: `lax.scan` over the
        rounds with the eval gated per round by a static flag array (the
        non-eval branch is never executed, only compiled), stacking per-node
        accuracy/loss — and the per-round accounting extras (fired edges,
        live edges, simulated time) — as scan outputs."""
        key = (rounds, eval_every)
        cached = self._fused_cache.get(key)
        if cached is not None:
            return cached
        evals = set(Schedule.eval_rounds(rounds, eval_every))
        flags = np.asarray([1 if r in evals else 0 for r in range(rounds)],
                           np.int32)
        round_fn = self._round_raw
        eval_fn = self._eval_raw
        # telemetry's params probes share the eval's static gate: the
        # untaken branch returns structural zeros, so non-eval rounds
        # never execute the flatten + norm traffic
        probes_fn = self._probes_raw
        probe_zeros = (self.bound_obs.probe_zeros()
                       if probes_fn is not None else {})
        x_test, y_test, n = self.x_test, self.y_test, self.n
        n_states = sum(self._state_flags())

        @jax.named_scope(spans.EVAL)
        def _eval_on(p):
            acc, loss = eval_fn(p, x_test, y_test)
            return acc, loss, (probes_fn(p) if probes_fn is not None
                               else {})

        def _eval_off(p):
            return (jnp.zeros((n,), jnp.float32),
                    jnp.zeros((n,), jnp.float32), probe_zeros)

        def gated_eval(flag, params):
            return jax.lax.cond(flag > 0, _eval_on, _eval_off, params)

        def body(carry, xs):
            r, flag = xs
            params, opt = carry[:2]
            states, rng = carry[2:2 + n_states], carry[-1]
            out = round_fn(params, opt, *states, r, rng)
            carry = out[:2 + n_states] + (out[2 + n_states],)  # ... + rng
            extras = out[4 + n_states:]  # everything past the loss slot
            acc, loss, probes = gated_eval(flag, carry[0])
            ys = (acc, loss) + tuple(extras)
            if probes_fn is not None:
                ys = ys + (probes,)
            return carry, ys

        def program(carry):
            return jax.lax.scan(
                body, carry,
                (jnp.arange(rounds, dtype=jnp.int32), jnp.asarray(flags)))

        fused = jax.jit(program, donate_argnums=(0,))
        self._fused_cache[key] = fused
        return fused

    def _account_comm(self, sent_edges, trig):
        """Identical (order-preserving) float accounting in both modes —
        the byte multiply stays in Python so exact accounting survives past
        f32's 2^24 integers."""
        self.comm_bytes_total += self.transport.payload_bytes * float(
            sent_edges)
        self._trig_sum += float(trig)
        self._comm_rounds += 1
        self.trig_history.append(float(trig))

    def _account_live(self, live_edges):
        """Dynamics accounting: the round's realized fraction of the static
        layout's directed edges (same Python-side discipline as comm)."""
        frac = float(live_edges) / max(self._total_directed, 1.0)
        self._live_sum += frac
        self._live_rounds += 1
        self.live_history.append(frac)

    def _account_time(self, sim_t, arrived_edges):
        """Event-clock accounting: `sim_t` is the ABSOLUTE simulated time at
        the end of the round; `arrived_edges` counts live directed edges
        whose payload made the deadline (all of them in synchronous mode).
        The arrived fraction is against the round's live edges under a
        dynamics process, the full static layout otherwise."""
        self.sim_time = float(sim_t)
        self.sim_time_history.append(self.sim_time)
        denom = (self.live_history[-1] * self._total_directed
                 if self.bound_dyn is not None else self._total_directed)
        frac = float(arrived_edges) / max(denom, 1.0)
        self._arrived_sum += frac
        self._arrived_rounds += 1
        self.arrived_history.append(frac)

    def _account_obs(self, snapshot):
        """Telemetry accounting: keep the round's layout-native channel
        snapshot (numpy) — `RoundMetrics.detail` and the trace exporter
        materialize from these on the host."""
        self.obs_history.append(jax.tree.map(np.asarray, snapshot))

    def _account_extras(self, extras):
        """Route one round's extras group-by-group (the generic convention:
        (sent, trig | live | sim_t, arrived | obs_snapshot) for the
        present subsystems)."""
        extras = list(extras)
        if self.transport is not None:
            self._account_comm(extras.pop(0), extras.pop(0))
        if self.bound_dyn is not None:
            self._account_live(extras.pop(0))
        if self.bound_timing is not None:
            self._account_time(extras.pop(0), extras.pop(0))
        if self.bound_obs is not None:
            self._account_obs(extras.pop(0))
        assert not extras

    def _finish_metrics(self, m: RoundMetrics, history, verbose,
                        probes=None):
        if self.transport is not None:
            m.bytes_on_wire = self.comm_bytes_total
            m.triggered_frac = self._trig_sum / max(self._comm_rounds, 1)
        if self.bound_dyn is not None:
            m.live_edge_frac = self._live_sum / max(self._live_rounds, 1)
        if self.bound_timing is not None:
            m.sim_time = self.sim_time
            m.arrived_frac = self._arrived_sum / max(self._arrived_rounds, 1)
        if self.bound_obs is not None and self.obs_history:
            m.detail = self.bound_obs.materialize(
                self.obs_history[-1], acc_per_node=m.acc_per_node,
                probes=probes)
        history.append(m)
        if self.ledger is not None:
            self.ledger.write(round_record(m))
        if verbose:
            log_round(self.method.name, m)

    def _carry(self):
        return (self.params, self.opt_state) + self._get_states() \
            + (self.rng,)

    def compile(self, rounds: Optional[int] = None,
                eval_every: Optional[int] = None):
        """AOT-lower and compile the fused schedule program for the current
        state — the SAME jitted program `run()` dispatches (same jaxpr,
        donation honored) — and keep the executable for every later
        `run()` of that schedule.  Returns it (`.as_text()` is the
        optimized HLO); compiling outside `run()` separates the compile
        seconds from the dispatch.  `compile_stats` keeps what the compile
        counters (`repro.obs.spans`) moved in this call: lowering seconds
        apart from the backend's load or compile."""
        rounds = self.schedule.rounds if rounds is None else rounds
        eval_every = (self.schedule.eval_every if eval_every is None
                      else eval_every)
        fused = self._fused_program(rounds, eval_every)
        before = spans.counters()
        if not isinstance(fused, jax.stages.Compiled):
            with spans.span("dfl.compile.lower"):
                lowered = fused.lower(self._carry())
            with spans.span("dfl.compile.load"):
                fused = lowered.compile()
            self._fused_cache[(rounds, eval_every)] = fused
        self.compile_stats = spans.counter_diff(spans.counters(), before)
        return fused

    def _run_fused(self, rounds, eval_every, verbose) -> List[RoundMetrics]:
        n_states = sum(self._state_flags())
        fused = self._fused_program(rounds, eval_every)
        with spans.span("dfl.run.dispatch"):
            carry, ys = fused(self._carry())
        self.params, self.opt_state = carry[:2]
        self._set_states(carry[2:2 + n_states])
        self.rng = carry[-1]
        with spans.span("dfl.run.fetch"):
            acc_r, loss_r = np.asarray(ys[0]), np.asarray(ys[1])
            # the telemetry extras group is a DICT of stacked arrays —
            # convert per leaf (scalars and dicts alike), not per group
            extras_r = [jax.tree.map(np.asarray, e) for e in ys[2:]]
        # the eval-gated params probes ride as the LAST scan output, after
        # the round extras (zeros on non-eval rounds — never read there)
        probes_r = extras_r.pop() if self._probes_raw is not None else None

        evals = set(Schedule.eval_rounds(rounds, eval_every))
        history: List[RoundMetrics] = []
        with spans.span("dfl.run.account"):
            for r in range(rounds):
                self._account_extras(
                    [jax.tree.map(lambda a: a[r], e) for e in extras_r])
                if r in evals:
                    m = RoundMetrics(round=r, acc_per_node=acc_r[r],
                                     loss_per_node=loss_r[r])
                    probes = (jax.tree.map(lambda a: a[r], probes_r)
                              if probes_r is not None else None)
                    self._finish_metrics(m, history, verbose, probes=probes)
        return history

    def _run_loop(self, rounds, eval_every, verbose) -> List[RoundMetrics]:
        evals = set(Schedule.eval_rounds(rounds, eval_every))
        n_states = sum(self._state_flags())
        history: List[RoundMetrics] = []
        for r in range(rounds):
            out = self._round(self.params, self.opt_state,
                              *self._get_states(), jnp.int32(r), self.rng)
            self.params, self.opt_state = out[:2]
            self._set_states(out[2:2 + n_states])
            self.rng = out[2 + n_states]
            self._account_extras(out[4 + n_states:])
            if r in evals:
                m = self.evaluate()
                m.round = r
                probes = (jax.tree.map(np.asarray,
                                       self._probes(self.params))
                          if self._probes is not None else None)
                self._finish_metrics(m, history, verbose, probes=probes)
        return history

    def run(self, rounds: Optional[int] = None,
            eval_every: Optional[int] = None, verbose: bool = False,
            mode: Optional[str] = None) -> List[RoundMetrics]:
        """Run the schedule; returns the eval history (includes round 0 =
        after the initial local training, matching the paper's Fig. 1
        x-axis).  Repeated calls continue from the current state (round
        indices restart, so the deterministic batch schedule repeats).
        Step t of a call reads each node's minibatch as one window of its
        stride-ordered shard (`data.pipeline.Batcher`), whose int32 start
        wraps only past 2**31 / batch_size steps; the earlier per-row
        gather's int32 index product wrapped after 2,118 rounds a call at
        4 steps of batch 32, so calls that long read other samples then.

        Verbose round lines go through the ``repro.obs.round`` logging
        stream
        (same text as always), the JSONL ledger gets one record per eval
        round plus a run summary (wall seconds, rounds/sec, compile-time
        counters), and `Telemetry(profile_dir=...)` wraps the run in a
        `jax.profiler` capture."""
        rounds = self.schedule.rounds if rounds is None else rounds
        eval_every = (self.schedule.eval_every if eval_every is None
                      else eval_every)
        mode = self.schedule.mode if mode is None else mode
        if mode not in SCHEDULE_MODES:
            raise ValueError(f"schedule mode must be one of {SCHEDULE_MODES}, "
                             f"got {mode!r}")
        profile = contextlib.nullcontext()
        if self.telemetry is not None and self.telemetry.profile_dir:
            profile = jax.profiler.trace(self.telemetry.profile_dir)
        before = spans.counters()
        t0 = _time.perf_counter()
        with profile, spans.span("dfl.run"):
            if mode == "fused":
                history = self._run_fused(rounds, eval_every, verbose)
            else:
                history = self._run_loop(rounds, eval_every, verbose)
        wall = _time.perf_counter() - t0
        if self.ledger is not None:
            stats = spans.counter_diff(spans.counters(), before)
            requests, hits = (int(stats["compile_requests"]),
                              int(stats["cache_hits"]))
            self.ledger.write({
                "kind": "summary", "mode": mode, "rounds": int(rounds),
                "wall_s": wall, "rounds_per_sec": rounds / max(wall, 1e-9),
                # a persistent-cache load is a request but not a compile
                "cold_compile": requests > hits,
                "compile_s": stats["lower_s"] + stats["load_s"],
                "lower_s": stats["lower_s"], "load_s": stats["load_s"],
                "compile_requests": requests, "cache_hits": hits,
                "cache_retrieval_s": stats["cache_retrieval_s"]})
        return history
