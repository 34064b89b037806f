"""``collective_ms.train4``: device time per federation period of the
collectives across chips (all-reduce, all-gather, reduce-scatter,
collective-permute, all-to-all) under the agent-sharding rules, averaged
over the chips."""
import re

LAYER = "sync across chips"
UNIT = "ms/period"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"
COLLECTIVE = re.compile(
    r"^%(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def read(out, reduced, cell, peaks):
    secs = sum(s for _, _, s in reduced.op_stats(
        lambda t: bool(COLLECTIVE.match(t))))
    periods = out.layer["periods"]
    if not periods:
        return None
    return 1e3 * secs / len(reduced.devices) / periods
