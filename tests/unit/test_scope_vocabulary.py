"""The closed vocabulary of device-side scopes
(`deepspeed_tpu/telemetry/scopes.py`): every ``ds_*`` scope or kernel
name of the tree is in it, the docs list it, and no name of it changes
what an accepted benchmark metric sums. One case a name."""

import glob
import json
import os
import re

import pytest

from deepspeed_tpu.telemetry import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SUITE = os.path.join(ROOT, "benchmarks", "suite")

# a string literal (or the fixed part of an f-string) that is a scope
LITERAL = re.compile(r'''["'](ds_[a-z0-9_{}]+)["']''')
# ds_* strings of the tree that name no device-side scope
NOT_SCOPES = {"ds_tpu", "ds_tpu_", "ds_config", "ds_tpu_audit",
              "ds_tpu_metrics",
              "ds_tpu_serve", "ds_tpu_run", "ds_tpu_tune", "ds_tpu_lint",
              "ds_tpu_step_seconds"}


def literals():
    """``{literal: [files]}`` of the tree's ``ds_*`` string literals
    (the table's own file left out); an f-string's ``{...}`` stands for
    any run of name characters."""
    found = {}
    for path in glob.glob(os.path.join(ROOT, "deepspeed_tpu", "**", "*.py"),
                          recursive=True):
        if path.endswith(os.path.join("telemetry", "scopes.py")):
            continue
        with open(path) as f:
            for lit in LITERAL.findall(f.read()):
                if lit not in NOT_SCOPES:
                    found.setdefault(lit, []).append(
                        os.path.relpath(path, ROOT))
    return found


def accepted_strings():
    """``{scope string: [metric files]}`` of what the benchmark's files
    sum by: every accepted metric file's ``scopes``. (A workload's
    ``scope_marker`` only filters the driver's own map: a name that
    holds one enlarges that map, and what an accepted metric then sums
    is decided by its ``scopes``, checked here.)"""
    out = {}
    for path in glob.glob(os.path.join(SUITE, "metrics", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] == "program_layer_time":
            continue        # the vocabulary's own reader: exact names
        for s in spec["args"].get("scopes", []):
            out.setdefault(s, []).append(os.path.basename(path))
    return out


LITERALS = literals()
ACCEPTED = accepted_strings()
# named before the vocabulary was closed (PR 53): the accepted metrics
# were written against these, nested as they are
BEFORE = {
    "ds_moe_route", "ds_moe_dispatch", "ds_moe_experts", "ds_moe_combine",
    "ds_moe_shared", "ds_moe_latent_down", "ds_moe_latent_up",
    "ds_moe_unwritten_rows", "ds_ssm_in_proj", "ds_ssm_conv",
    "ds_ssm_scan", "ds_ssm_gate_norm", "ds_ssm_out_proj", "ds_ssd_prefill",
    "ds_ssm_decode", "ds_gdn_conv", "ds_gdn_scan", "ds_gdn_step",
    "ds_gated_delta_chunked", "ds_gdn_step_rows", "ds_mla_project",
    "ds_mla_prefill_attn", "ds_mla_decode_attn", "ds_attn_gate",
    "ds_attn_decode_full", "ds_attn_decode_window", "ds_attn_prefill_full",
    "ds_attn_prefill_window", "ds_flash_fwd", "ds_flash_dq", "ds_flash_dkv",
    "ds_flash_decode_paged", "ds_flash_prefill_latent",
    "ds_window_prefill_band"}


def test_the_tree_has_literals_and_the_benchmark_has_strings():
    assert len(LITERALS) >= 40 and len(ACCEPTED) >= 15
    assert "ds_attn_decode_{kind}" in LITERALS


@pytest.mark.parametrize("literal", sorted(LITERALS))
def test_every_literal_of_the_tree_is_in_the_vocabulary(literal):
    rx = re.compile(re.sub(r"\{[^}]*\}", "[a-z0-9_]+", literal))
    hits = [s for s in scopes.SCOPES if rx.fullmatch(s)]
    assert hits, (literal, LITERALS[literal])
    if "{" in literal:      # each form an f-string can take is listed
        assert len(hits) >= 2, (literal, hits)


@pytest.mark.parametrize("name", sorted(scopes.SCOPES))
def test_every_name_is_used_documented_and_laid_to_a_layer(name):
    layer, covers = scopes.SCOPES[name]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        layers = {m["layer"] for m in json.load(f)["per_layer"]}
    assert layer in layers and covers
    used = any(re.fullmatch(re.sub(r"\{[^}]*\}", "[a-z0-9_]+", lit), name)
               for lit in LITERALS)
    assert used, f"{name} is in SCOPES and nowhere in the tree"
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        assert f"`{name}`" in f.read()


@pytest.mark.parametrize("name", sorted(set(scopes.SCOPES) - BEFORE))
def test_a_new_name_changes_no_accepted_sum(name):
    """Rule (b): a new name neither contains nor is contained in a scope
    string an accepted metric lists (a substring match would then take
    it, or be taken by it). A metric written since the vocabulary was
    closed sums a name of it, exactly: that name it may be, and a kernel
    that lies inside that scope may carry it as a prefix
    (``ds_kda_scan_chunks`` under ``ds_kda_scan``)."""
    for accepted, where in ACCEPTED.items():
        if accepted not in BEFORE and (
                name == accepted or name.startswith(accepted + "_")):
            continue
        assert accepted not in name and name not in accepted, \
            (name, accepted, where)


def test_the_older_names_are_the_vocabulary_s():
    """The metrics from before the vocabulary was closed sum older
    names; a later metric sums names of the vocabulary, whole."""
    assert BEFORE < set(scopes.SCOPES)
    assert set(ACCEPTED) - BEFORE <= set(scopes.SCOPES) - BEFORE


def test_innermost_and_chain():
    op = ("jit(_decode_fn)/jit(main)/LM/layers_3/ds_experts/experts/"
          "jit(_held_experts)/ds_moe_experts/ragged_dot")
    assert scopes.chain(op) == ["ds_experts", "ds_moe_experts"]
    assert scopes.innermost(op) == "ds_moe_experts"
    assert scopes.innermost("jit(f)/transpose(jvp(ds_mlp))/mul") == "ds_mlp"
    assert scopes.innermost("jit(f)/ds_tpu/ds_unknown_scope/add") is None
    assert scopes.innermost("") is None and scopes.innermost(None) is None
