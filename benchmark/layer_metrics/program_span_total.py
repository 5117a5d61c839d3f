"""`program_span_stat`'s `total_per` for a kind that may never occur:
a program that registers the kind and never began it in the window
reads 0.0 (a wait that never happened is a finding); a program without
the kind reads nothing. params: `kind`, `total_per`."""

from benchmark.layer_metrics import program_span_stat


def read(readings, params):
    from tendermint_tpu.libs import tracing

    got = program_span_stat.read(readings, params)
    if got is None and params["kind"] in tracing.registered_kinds():
        return 0.0, {"entries": 0}
    return got
