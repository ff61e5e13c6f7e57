"""repro.obs — scan-native observability: telemetry channels, the run
ledger, event-clock trace export, and the program's own performance spans.

    from repro.obs import Telemetry
    world = World.synthetic(nodes=16, telemetry=Telemetry(
        channels="auto", ledger="run.jsonl"))
    exp = Experiment(world, "decdiff+vt", comm=CommConfig(codec="int8"))
    hist = exp.run()
    hist[-1].detail["consensus"]             # per-node ‖w_i − w̄‖
    export_trace(exp, "trace.json")          # open in Perfetto

Opt-in and zero-cost when off: the channel accumulators ride the engine's
one `lax.scan` carry (no host syncs mid-run, no rng consumed), and
`telemetry=None` is bit-identical to an engine without this package —
pinned across backends × layouts × schedule modes in tests/test_obs.py.
See docs/observability.md for the channel catalog, the ledger schema, and
a trace-export worked example.

`repro.obs.spans` is always on: named device scopes for the round's
phases, host spans on the profiler's clock, and compile counters.
"""
from repro.obs.channels import (  # noqa: F401
    CHANNELS,
    BoundTelemetry,
    ChannelSpec,
    Telemetry,
    available_channels,
    channels_for,
)
from repro.obs.ledger import (  # noqa: F401
    MANIFEST_EDGE_CAP,
    SCHEMA,
    SCHEMA_VERSION,
    RunLedger,
    format_round,
    get_round_logger,
    log_round,
    read_ledger,
    round_record,
    run_manifest,
    validate_ledger,
    validate_record,
)
from repro.obs.spans import (  # noqa: F401
    SCOPES,
    compile_times,
    counter_diff,
    counters,
    span,
    span_table,
)
from repro.obs.trace import build_trace, export_trace  # noqa: F401
