"""Share of its roofline that the embed forward of a model with expert
layers reaches: the least time the chip could take for the traced work, over
the device time of the programs whose name holds the family's ``PROGRAM`` in
the traced window.

The least time is ``workcount.least_time`` of the operations the traced
requests' queries need (``workcount_embed.embed_flops`` over their real
tokens, at the family's ``flops_per_token``, which counts the experts a token
is routed to and no other) and of the parameter bytes the traced
``embed.dispatch`` spans had to read (the family's ``param_bytes_per_call`` of
each span's real ``tokens``: every parameter outside the experts and the
experts its tokens can reach). Both are counted from shapes and real tokens,
so padding, recomputation and a program that computes every expert for every
token lower the share, and nothing reads over 100. A family without
``param_bytes_per_call`` (no expert layer), a trace without the program, or
spans without ``tokens`` give nothing to read."""

import functools
import sys

from lib import program_spans as ps
from lib import workcount, workcount_embed, xplane


def read(trace, spans, counts, cell):
    fam = cell["family"]
    if (not trace or trace.get("stand_in") or not cell.get("chip")
            or not hasattr(fam, "param_bytes_per_call")):
        return None
    t = xplane.program_time(trace, fam.PROGRAM)
    tokens = counts.get("traced_tokens")
    sent = [s["args"]["tokens"] for s in ps.named(ps.load(cell), "embed.dispatch")
            if "tokens" in s["args"]]
    if not t or not t["total_s"] or not tokens or not sent:
        return None
    model = cell["config"]
    flops = workcount_embed.embed_flops(tokens, functools.partial(fam.flops_per_token, model))
    nbytes = sum(fam.param_bytes_per_call(model, n) for n in sent)
    least, bound = workcount.least_time(flops, nbytes, cell["chip"])
    print(f"[layer] {fam.PROGRAM}: {sum(tokens)} real tokens of {len(tokens)} traced requests "
          f"({flops / 1e12:.2f} TFLOP), {len(sent)} dispatches reading {nbytes / 1e9:.2f} GB; "
          f"least {least * 1e3:.2f} ms ({bound}), device {t['total_s'] * 1e3:.2f} ms over "
          f"{t['calls']} calls", file=sys.stderr)
    return 100.0 * least / t["total_s"]
