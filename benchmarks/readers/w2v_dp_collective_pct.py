"""``w2v_dp_collective_pct``: the share of the traced window a chip
spends in the worker-to-server exchange of the data-parallel trainer:
the summed device seconds of the collective leaf ops over chips and
window, in percent. An op counts by its HLO opcode, the word before the
operands in ``tracered``'s op label, and not by its name alone: the two
once-a-dispatch table psums are named ``psum_invariant.<n>`` and are
``all-reduce(bf16[1500000,300])``, which ``tracered``'s ``collective_s``
(it matches names) leaves out. A sum, not a union: collectives that
overlap each other on one chip count twice. No collective in the trace:
no value."""

import re

_KINDS = (r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
          r"collective-permute|collective-broadcast|send|recv)")
# ``<name> <result> <opcode>(<operands>)``; a label cut short keeps the name
_OPCODE = re.compile(r"\s" + _KINDS + r"(-start|-done)?\(")
_NAME = re.compile(_KINDS + r"([-. ]|$)")


def read(ctx):
    t = ctx.tracered
    if not t or not t.get("ops") or not t.get("chips") \
            or t["window_s"] <= 0:
        return None
    seconds = sum(s for label, s in t["ops"].items()
                  if _OPCODE.search(label) or _NAME.match(label))
    if seconds <= 0:
        return None
    return 100.0 * seconds / (t["chips"] * t["window_s"])
