"""Regenerate paddle_tpu/ops/_yaml_ops.py (when the reference checkout is
present) and OPS_PARITY.md from paddle_tpu.ops.parity.

Usage: python tools/gen_ops_parity.py [--reference /root/reference]
"""
import argparse
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

YAMLS = ["ops.yaml", "legacy_ops.yaml", "static_ops.yaml",
         "fused_ops.yaml", "sparse_ops.yaml"]


def refresh_snapshot(ref_root):
    ydir = os.path.join(ref_root, "paddle/phi/api/yaml")
    if not os.path.isdir(ydir):
        print(f"reference yaml dir {ydir} absent; keeping snapshot")
        return
    names = {}
    for f in YAMLS:
        with open(os.path.join(ydir, f)) as fh:
            names[f] = re.findall(r"^- op\s*:\s*(\w+)", fh.read(), re.M)
    out = os.path.join(os.path.dirname(__file__),
                       "../paddle_tpu/ops/_yaml_ops.py")
    with open(out, "w") as fh:
        fh.write('"""Snapshot of the reference\'s PHI forward-op YAML '
                 'surface.\n\nGenerated by tools/gen_ops_parity.py. '
                 'Regenerate when the reference moves.\n"""\n\n')
        fh.write("YAML_OPS = {\n")
        for fn, ops in names.items():
            fh.write(f"    {fn!r}: [\n")
            line = "        "
            for o in ops:
                tok = f"{o!r}, "
                if len(line) + len(tok) > 76:
                    fh.write(line.rstrip() + "\n")
                    line = "        "
                line += tok
            if line.strip():
                fh.write(line.rstrip() + "\n")
            fh.write("    ],\n")
        fh.write("}\n")
    print(f"snapshot refreshed: {sum(len(v) for v in names.values())} names")


def render_md():
    from paddle_tpu.ops.parity import classify
    table, unmapped = classify()
    kinds = {"registry": 0, "alias": 0, "excluded": 0}
    for k, _, _ in table.values():
        kinds[k] += 1
    lines = [
        "# OPS_PARITY — reference PHI YAML op surface vs paddle_tpu",
        "",
        "Machine-generated by `tools/gen_ops_parity.py` from "
        "`paddle_tpu/ops/parity.py`;",
        "checked in CI by `tests/test_ops_parity.py` (every row must "
        "resolve, unmapped == 0).",
        "",
        f"**{len(table)} YAML forward ops**: {kinds['registry']} registry "
        f"ops, {kinds['alias']} API aliases, {kinds['excluded']} "
        f"documented exclusions, {len(unmapped)} unmapped.",
        "",
        "| yaml op | kind | maps to / reason | declared in |",
        "|---|---|---|---|",
    ]
    for name, (kind, detail, files) in sorted(table.items()):
        srcs = ", ".join(f.replace("_ops.yaml", "").replace(".yaml", "")
                         for f in files)
        lines.append(f"| `{name}` | {kind} | {detail} | {srcs} |")
    for name in unmapped:
        lines.append(f"| `{name}` | **UNMAPPED** | — | |")
    from paddle_tpu.ops.parity import BEYOND_YAML
    lines += [
        "",
        "## Registry ops beyond the YAML surface",
        "",
        "The ops the benchmark's model families brought "
        "(`paddle_tpu/ops/parity.py:BEYOND_YAML`).",
        "",
        "| op | written in | kernel on a TPU | called by |",
        "|---|---|---|---|",
    ]
    for name, (where, kernel, caller) in sorted(BEYOND_YAML.items()):
        lines.append(f"| `{name}` | {where} | {kernel} | {caller} |")
    out = os.path.join(os.path.dirname(__file__), "../OPS_PARITY.md")
    with open(out, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    print(f"OPS_PARITY.md written: {len(table)} rows, "
          f"{len(unmapped)} unmapped")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--reference", default="/root/reference")
    args = ap.parse_args()
    refresh_snapshot(args.reference)
    render_md()
