"""How the trace names the program's kernels and step programs.

A Pallas kernel's device op is a custom call named after the jitted
function around its ``pallas_call`` (``_matmul.64``, ``_decode_attention.9``
in a v5e trace); a jitted step's ops lie inside its XLA module's event
(``jit_decode_step(<fingerprint>)``).  These patterns are the only
place the benchmark reads the program's names.
"""
import re

KERNELS = {
    "matmul": re.compile(r"^_matmul(\.\d+)?$"),
    "decode_attention": re.compile(r"^_decode_attention(\.\d+)?$"),
    "prefill_attention": re.compile(r"^_prefill_attention(\.\d+)?$"),
    "flash_bwd": re.compile(r"^_flash_attention_bwd"),
}
MODULES = {
    "decode": re.compile(r"^jit_decode_step\b"),
    "prefill": re.compile(r"^jit_prefill_step_paged\b"),
}


def match(kernel: str, module: str = ""):
    """A predicate on (op name, module name) for ``trace.op_seconds``."""
    k = KERNELS[kernel]
    m = MODULES[module] if module else None
    return lambda name, mod: bool(k.search(name)) and (
        m is None or bool(m.search(mod)))
