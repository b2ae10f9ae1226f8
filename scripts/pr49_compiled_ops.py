#!/usr/bin/env python3
"""The operations of a compiled serving program as a profiler would name
them (`%name = shape op(shape %operand, ...)`: the instruction with its
operands' shapes, fusions' bodies left out), with the compiler's own cycle
estimate of each, from the text `scripts/pr49_compile_for_v5e.py --text`
writes for a DESCRIBED v5e. No chip, nothing ran: the cycles are the
compiler's guess and no time. What `benchmark/tests/data/
xing_compiled_ops.json.gz` was made with, for the test that holds the
hyper readers' patterns to the names the chip's compiler gives.

    python3 scripts/pr49_compiled_ops.py <program>.hlo.txt [...] > ops.json
"""
import json
import re
import sys

SKIP = {"parameter", "get-tuple-element", "tuple", "bitcast", "constant",
        "while", "conditional", "call"}


def ops_of(text):
    shape, inside, rows, comp = {}, {}, [], None
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    for ln in text.splitlines():
        m = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", ln)
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) "
                     r"([\w\-]+)\((.*)$", ln)
        if m:
            shape[m.group(1)] = m.group(2)
            inside[m.group(1)] = comp
            rows.append(m.groups() + (ln,))
    out = []
    for name, shp, op, rest, ln in rows:
        if inside[name] in fused or op in SKIP:
            continue
        args = re.findall(r"%[\w.\-]+", rest.split("), ")[0])
        cyc = re.search(r'"estimated_cycles":"(\d+)"', ln)
        out.append([f"{name} = {shp} {op}(" + ", ".join(
            f"{shape.get(a, '?')} {a}" for a in args) + ")",
            int(cyc.group(1)) if cyc else 0])
    return out


if __name__ == "__main__":
    json.dump({p.rsplit("/", 1)[-1]: ops_of(open(p).read())
               for p in sys.argv[1:]}, sys.stdout)
