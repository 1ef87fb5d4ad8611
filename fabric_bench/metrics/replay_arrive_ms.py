"""Host milliseconds per request the flow-credit replay spends entering
arriving flows into its ledger: the ``arrive_ns`` counter of the
program's ``fabric.replay`` spans."""

from fabric_bench import spans


def read(ctx):
    ns = spans.mean_attr(ctx, "fabric.replay", "arrive_ns")
    return None if ns is None else ns * 1e-6
