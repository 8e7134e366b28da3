"""``collective_ms.<cells>``: device milliseconds per unit of collective
ops, on the device with the most."""

import re

#: HLO collectives, with their asynchronous start/done halves
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|send|recv)")


def matches(event) -> bool:
    return bool(COLLECTIVE.match(event.opcode))


def read(ctx, variant=None):
    if ctx.trace is None:
        return None
    most = max(ctx.trace.seconds(matches))
    return 1e3 * most / ctx.trace.units if most > 0 else None
