"""Share of the chip's bfloat16 peak that the embed forward reaches: the
operations the traced requests' queries need (``workcount_embed.embed_flops``
over their real tokens, at the family's ``flops_per_token``: no padding, no
bucket, each text's attention over its own length) over the peak times all the
device time of the programs whose name holds the family's ``PROGRAM`` in the
traced window. The forward is compute-bound from a few hundred tokens a call
on (below that it is bound by one read of its parameters), so the compute peak
is the roof. It counts real tokens, so padding and recomputation lower it and
nothing reads over 100; where the trace holds no such program there is nothing
to read."""

import functools
import sys

from lib import workcount_embed, xplane


def read(trace, spans, counts, cell):
    if not trace or trace.get("stand_in") or not cell.get("chip"):
        return None
    fam = cell["family"]
    t = xplane.program_time(trace, fam.PROGRAM)
    tokens = counts.get("traced_tokens")
    if not t or not t["total_s"] or not tokens:
        return None
    flops = workcount_embed.embed_flops(
        tokens, functools.partial(fam.flops_per_token, cell["config"]))
    least = flops / cell["chip"]["bf16_flops"]
    print(f"[layer] {fam.PROGRAM}: {sum(tokens)} real tokens of {len(tokens)} traced requests, "
          f"least {least * 1e3:.2f} ms, device {t['total_s'] * 1e3:.2f} ms over "
          f"{t['calls']} calls", file=sys.stderr)
    return 100.0 * least / t["total_s"]
