"""How the trace names the program's collectives across chips.

On a TPU a collective is an XLA op named after its HLO instruction: the
tensor-parallel serving steps' all-gather of the attention heads and
all-reduce of the MLP output (``all-gather.7``, ``psum.17`` in the tp4
decode step's compiled HLO, the all-reduce named after the ``psum`` that
made it), and their asynchronous halves where the compiler splits them
(``all-gather-start``/``-done``, ``all-reduce-start``/``-done``).  These
patterns are the only place the benchmark reads those names.
"""
import re

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|psum|all_gather)(-start|-done)?([.:]\d+)?$")


def match(module=None):
    """A predicate on (op name, module name) for ``trace.op_seconds``:
    the collectives, within ``module`` (a compiled pattern) if given."""
    return lambda name, mod: bool(COLLECTIVE.search(name)) and (
        module is None or bool(module.search(mod)))
