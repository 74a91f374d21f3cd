"""Reading a compiled program's optimized HLO text: the instructions
that run as operations of their own (what a device trace shows as
events), apart from those fused into another."""
import functools
import re

# what the hand-over between the delta-rule mixer's kernel families must
# not copy (PERF.md section 6, PR 41), by dtype and dimensions: q / k and
# v between the convolution's kernels and the rule's as PR 40's trace
# named them — (B T / 8, 8, heads, 128), the token-major array retiled by
# heads —, their cotangents, a row of either inside the rule's loop over
# rows, and the rule's output on its way into ``gdn_out``'s norm
HANDOVER_SHAPES = (
    "bf16[4096,8,16,128]", "bf16[4096,8,32,128]",
    "bf16[4,8192,2048]", "bf16[4,8192,4096]",
    "bf16[1,8192,2048]", "bf16[1,8192,4096]",
    "bf16[1024,8,16,128]", "bf16[1024,8,32,128]",
    "bf16[4,8192,16,128]", "bf16[4,8192,32,128]",
    "f32[4096,8,32,128]", "f32[4,8192,32,128]")
MIXER_SCOPES = ("gdn_conv", "gdn_scan", "gdn_out")


@functools.lru_cache(maxsize=4)
def unfused_instructions(hlo_text):
    """(name, shape with layout, opcode, op_name) of every instruction
    outside a fusion's computation (kept: a program's text is megabytes
    and a test asks once a shape)."""
    fused = set(re.findall(r" fusion\([^\n]*calls=%([\w.\-]+)", hlo_text))
    out, computation = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            computation = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if m and computation not in fused:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((*m.groups(), op.group(1) if op else ""))
    return tuple(out)


def handover_copies(hlo_text, shape: str, scopes=MIXER_SCOPES):
    """The ``copy``, ``reshape`` and ``transpose`` operations — each a
    pass over every byte; a reshape that changes nothing in memory is a
    ``bitcast`` by now — whose result is ``shape``, under one of
    ``scopes`` (the delta-rule mixer's unless named) or under no name,
    where the compiler puts a copy it makes for a layout of its own
    choosing."""
    return [(name, result, op) for name, result, opcode, op
            in unfused_instructions(hlo_text)
            if opcode in ("copy", "reshape", "transpose")
            and result.startswith(shape + "{")
            and (not op or any(f"{s}/" in op or f"({s})" in op
                               for s in scopes))]
