"""Machine check of the op-parity audit (VERDICT r3 missing #2): every
forward op in the reference's five PHI YAML files must map to a registry
op, a resolvable API path, or a documented exclusion — and the doc
generator must agree with the live classification."""
import paddle_tpu  # noqa: F401  (populate the registry)
from paddle_tpu.ops.parity import (ALIASES, EXCLUDED, YAML_OPS, classify,
                                   resolve_api)


def test_every_yaml_op_is_mapped():
    table, unmapped = classify()
    assert len(unmapped) == 0, f"unmapped YAML ops: {unmapped}"
    assert len(table) == len({n for v in YAML_OPS.values() for n in v})


def test_alias_paths_resolve():
    dead = sorted(p for p in set(ALIASES.values()) if not resolve_api(p))
    assert not dead, f"alias paths that no longer import: {dead}"


def test_no_overlapping_or_stale_entries():
    from paddle_tpu.ops.registry import OPS
    # an alias or exclusion for a name the registry now provides is
    # stale bookkeeping — the registry entry must win and the row go
    stale_alias = sorted(n for n in ALIASES if n in OPS)
    stale_excl = sorted(n for n in EXCLUDED if n in OPS)
    both = sorted(set(ALIASES) & set(EXCLUDED))
    assert not stale_alias, f"aliases shadowed by registry: {stale_alias}"
    assert not stale_excl, f"exclusions shadowed by registry: {stale_excl}"
    assert not both, f"names in both ALIASES and EXCLUDED: {both}"


def test_snapshot_covers_all_five_yamls():
    assert set(YAML_OPS) == {"ops.yaml", "legacy_ops.yaml",
                             "static_ops.yaml", "fused_ops.yaml",
                             "sparse_ops.yaml"}
    assert sum(len(v) for v in YAML_OPS.values()) >= 560


def test_doc_is_in_sync():
    import os
    md = os.path.join(os.path.dirname(__file__), "..", "OPS_PARITY.md")
    assert os.path.exists(md), "run tools/gen_ops_parity.py"
    text = open(md).read()
    table, unmapped = classify()
    assert "UNMAPPED" not in text
    assert f"**{len(table)} YAML forward ops**" in text


def test_the_ops_beyond_the_yaml_surface_resolve_and_are_listed():
    """Every op of `BEYOND_YAML` is in the registry, in no YAML file, its
    source file exists, and OPS_PARITY.md has its row."""
    import os
    from paddle_tpu.ops.parity import BEYOND_YAML
    from paddle_tpu.ops.registry import OPS
    table, _unmapped = classify()
    root = os.path.join(os.path.dirname(__file__), "..")
    text = open(os.path.join(root, "OPS_PARITY.md")).read()
    assert {"gated_delta_rule", "moe_route", "cca_mix"} <= set(BEYOND_YAML)
    for name, (where, kernel, _caller) in BEYOND_YAML.items():
        assert name in OPS and name not in table, name
        assert os.path.isfile(os.path.join(root, "paddle_tpu", where)), where
        if kernel != "-":
            for path in (w for w in kernel.replace(",", " ").split()
                         if w.endswith(".py")):
                assert os.path.isfile(os.path.join(root, "paddle_tpu",
                                                   path)), path
        assert f"| `{name}` | {where} |" in text, name
