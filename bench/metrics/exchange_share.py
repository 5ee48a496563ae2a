"""Share of the devices' traced window that the collectives took, in %
(`exchange_share.lm4`): the push's route of the clients' gradients to the
server's blocks and the fetch's gather of the new W, with whatever other
collective XLA put in the step.

The device time of every op whose HLO opcode is a collective (all-to-all,
all-gather, all-reduce, reduce-scatter, collective-permute, their
asynchronous start and done halves included), summed over the devices,
over the window's seconds times the number of devices that ran ops.  The
reduction hands the readers op names only, so the collectives are found
by opcode; the scopes `push_route` and `fetch_gather` put them down to
their layer in `bench/scopes.py`.  A trace with no collective, as on one
chip, reads nothing.
"""
from bench import trace

COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")


def is_collective(name: str) -> bool:
    return trace.opcode(name).startswith(COLLECTIVES)


def read(ctx):
    seconds, count = ctx.op_time(is_collective)
    if not count:
        return None
    return 100.0 * seconds / (ctx.red["window_s"] * ctx.red["devices"])
