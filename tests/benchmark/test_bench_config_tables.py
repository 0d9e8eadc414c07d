"""A configuration's file carries its source's entry whole. Five rows of the
`model-configs` catalog (fixtures/catalog_rows.jsonl: name, source_url and
config, letter for letter), each written into a file with the harness's keys,
pass `run.check_sizes` with a reference module that brings the three tables and
with no edit to any file that is there, and pass `check_source.differs`; with
any one table entry gone, or any one value moved, the run would stop before an
engine is built."""

import copy
import dataclasses
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check_source  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from llm_mcp_tpu.models.configs import ModelConfig  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "catalog_rows.jsonl")
with open(FIXTURE) as _f:
    ROWS = {row["name"]: row for row in map(json.loads, _f)}

# What each row states that run.py's own tables do not know, and which of the
# module's tables holds it: HELD path -> the field of the (stub) program that
# computes with it, ONLY path -> the one value, STATED path -> the reason.
TABLES = {
    "Solar-Open2-250B": {
        "HELD": {"linear_attn_config.short_conv_kernel_size": "lin_conv", "gqa_interval": "gqa_interval",
                 "linear_attn_config.head_dim": "lin_head_dim", "gqa_layers": "gqa_layers",
                 "linear_attn_config.num_heads": "lin_heads", "use_gqa_gate": "attn_gate",
                 "linear_attn_config.num_kv_heads": "lin_kv_heads", "use_rope": "use_rope",
                 "kda_allow_neg_eigval": "lin_neg_eigval"},
        "ONLY": {"partial_rotary_factor": 1, "kda_use_full_proj": False},
        "STATED": {},
    },
    "Olmo-Hybrid-7B": {
        "HELD": {"layer_types": "layer_types", "linear_num_key_heads": "lin_k_heads",
                 "linear_num_value_heads": "lin_v_heads", "linear_key_head_dim": "lin_k_dim",
                 "linear_value_head_dim": "lin_v_dim", "linear_conv_kernel_dim": "lin_conv",
                 "linear_allow_neg_eigval": "lin_neg_eigval"},
        "ONLY": {"rope_parameters.rope_theta": None},
        "STATED": {},
    },
    "granite-4.0-h-micro": {
        "HELD": {"attention_multiplier": "attn_scale", "embedding_multiplier": "embed_mult",
                 "layer_types": "layer_types", "logits_scaling": "logit_div",
                 "mamba_conv_bias": "ssm_conv_bias", "mamba_d_conv": "ssm_conv", "mamba_d_head": "ssm_head_dim",
                 "mamba_d_state": "ssm_state", "mamba_expand": "ssm_expand", "mamba_n_groups": "ssm_groups",
                 "mamba_n_heads": "ssm_heads", "mamba_proj_bias": "ssm_proj_bias",
                 "position_embedding_type": "pos_kind", "residual_multiplier": "resid_mult",
                 "shared_intermediate_size": "shared_ffn_hidden"},
        "ONLY": {"normalization_function": "rmsnorm", "num_local_experts": 0},
        "STATED": {"mamba_chunk_size": "the block a scan is computed in: any block gives the same state"},
    },
    "SmallThinker-21BA3B-Instruct": {
        "HELD": {"moe_ffn_hidden_size": "moe_ffn_hidden", "moe_num_active_primary_experts": "experts_per_tok",
                 "moe_num_primary_experts": "n_experts", "moe_primary_router_apply_softmax": "router_softmax",
                 "rope_layout": "rope_layers", "sliding_window_layout": "window_layers",
                 "sliding_window_size": "sliding_window"},
        "ONLY": {},
        "STATED": {"model_name": "a label of the release, read by nothing"},
    },
    "Kimi-K2.5": {
        "HELD": {"scoring_func": "router_score", "topk_method": "router_topk"},
        "ONLY": {"encoder_no_repeat_ngram_size": 0, "ep_size": 1, "num_nextn_predict_layers": 0},
        "STATED": {"seq_aux": "a coefficient of the training loss", "top_k": "the sampler's default; a request states its own",
                   "tf_legacy_loss": "a switch of the training loss"},
    },
}
# The paths ISSUE 28 lists as what stopped each row at the parent: all of them are above.
NAMED_BY_THE_ISSUE = {
    "Solar-Open2-250B": ["gqa_interval", "partial_rotary_factor"],
    "Olmo-Hybrid-7B": ["linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
                       "linear_value_head_dim", "linear_conv_kernel_dim"],
    "granite-4.0-h-micro": ["mamba_d_state", "attention_multiplier", "embedding_multiplier",
                            "logits_scaling", "residual_multiplier"],
    "SmallThinker-21BA3B-Instruct": ["moe_num_primary_experts", "moe_ffn_hidden_size"],
    "Kimi-K2.5": ["encoder_no_repeat_ngram_size", "ep_size", "num_nextn_predict_layers", "top_k"],
}
MODULE = '''"""A reference module a model_config PR would add: the forward is not the
point here, the three tables are."""
SERVED_TOL_REL = 0.1
FIELDS = {held!r}
HELD = {{path: (lambda c, f=f: getattr(c, f)) for path, f in FIELDS.items()}}
ONLY = {only!r}
STATED = {stated!r}


def check(cfg):
    pass


def logits(cfg, params, tokens, rows, cols):
    raise NotImplementedError
'''


def config_file(name: str, **changes) -> dict:
    """The row's `config` plus the harness's keys: what a builder would write."""
    row = ROWS[name]
    body = {"name": name.lower(), "source": row["source_url"], **copy.deepcopy(row["config"]),
            "reduced": [], "assumed": ["seeded random weights"], "deployment": "a test",
            "weights_seed": 0, "reference": "tables",
            "program": {"engine": "generation", "env": {"TPU_MODEL": name.lower()}}}
    return dict(body, **changes)


LEFT_OUT = object()


def put(config: dict, path: str, value) -> dict:
    """A copy of the file with the value at a dotted path replaced, or left out."""
    out = copy.deepcopy(config)
    *groups, leaf = path.split(".")
    group = out
    for key in groups:
        group = group[key]
    if value is LEFT_OUT:
        del group[leaf]
    else:
        group[leaf] = value
    return out


def stub_program(name: str):
    """A `ModelConfig` that computes with what the row publishes: the real
    dataclass's fields where run.py's tables read them, and beside them the
    fields a `model_config` PR would add for this row's `HELD` paths."""
    config = ROWS[name]["config"]
    fields = dataclasses.asdict(ModelConfig(name=name))
    for key, field in bench_run.MODEL_KEYS.items():
        if key in config:
            fields[field] = config[key] or 0
    fields["n_kv_heads"] = config["num_key_value_heads"]
    fields.setdefault("resolved_head_dim", fields["dim"] // fields["n_heads"])  # a property there
    rope = config.get("rope_scaling") or {}
    fields.update({f: rope[k] for k, f in bench_run.ROPE_KEYS.items() if k in rope})
    for path, field in TABLES[name]["HELD"].items():
        fields[field] = check_source.lookup(config, path)
    return types.SimpleNamespace(**fields)


def load_module(tmp_path, monkeypatch, tables: dict):
    """The tables written as a reference module to a directory of the test's,
    and loaded as run.py loads the module a configuration's file names."""
    (tmp_path / "references").mkdir(exist_ok=True)
    (tmp_path / "references" / "tables.py").write_text(
        MODULE.format(held=tables["HELD"], only=tables["ONLY"], stated=tables["STATED"]))
    monkeypatch.setattr(bench_run, "HERE", str(tmp_path))
    return bench_run.load_reference({"reference": "tables", "program": {"engine": "generation"}})[1]


@pytest.mark.parametrize("name", sorted(TABLES))
def test_a_catalog_rows_file_is_held_whole(name, tmp_path, monkeypatch):
    module = load_module(tmp_path, monkeypatch, TABLES[name])
    config = config_file(name)
    unheld = bench_run.check_sizes(config, stub_program(name), module)
    for path, why in TABLES[name]["STATED"].items():  # what the run's log shows a reviewer
        assert f"{path} ({why})" in unheld
    assert "max_position_embeddings" in unheld
    assert check_source.differs(config, ROWS[name]) == []


@pytest.mark.parametrize("name", sorted(TABLES))
def test_run_pys_own_tables_stop_at_what_they_do_not_know(name):
    """With no table brought, the run stops at exactly the paths of TABLES:
    numbers inside groups, bools, lists and strings among them."""
    with pytest.raises(AssertionError) as err:
        bench_run.check_sizes(config_file(name), stub_program(name))
    paths = sorted(p for table in TABLES[name].values() for p in table)
    assert f"states {paths}, which check_sizes compares with nothing" in str(err.value)
    assert set(NAMED_BY_THE_ISSUE[name]) <= set(paths)


@pytest.mark.parametrize("name,table,path", [
    (name, table, path) for name in sorted(TABLES) for table in ("HELD", "ONLY", "STATED")
    for path in sorted(TABLES[name][table])])
def test_nothing_is_unread(name, table, path, tmp_path, monkeypatch):
    """Any one entry dropped from the module's tables: the run stops and names the path."""
    tables = {t: {p: v for p, v in entries.items() if p != path}
              for t, entries in TABLES[name].items()}
    module = load_module(tmp_path, monkeypatch, tables)
    with pytest.raises(AssertionError) as err:
        bench_run.check_sizes(config_file(name), stub_program(name), module)
    assert f"states ['{path}'], which check_sizes compares with nothing" in str(err.value)


def one_moved(values: list) -> list:
    return values[:-1] + [values[-1] + 1 if isinstance(values[-1], int) else "full_attention_2"]


@pytest.mark.parametrize("name,path,value", [
    ("Solar-Open2-250B", "linear_attn_config.head_dim", 64),  # a number inside a group
    ("Solar-Open2-250B", "linear_attn_config.num_kv_heads", 8),  # null in the source
    ("Solar-Open2-250B", "gqa_layers", one_moved(ROWS["Solar-Open2-250B"]["config"]["gqa_layers"])),
    ("Solar-Open2-250B", "gqa_layers", ROWS["Solar-Open2-250B"]["config"]["gqa_layers"][:-1]),
    ("Olmo-Hybrid-7B", "layer_types", one_moved(ROWS["Olmo-Hybrid-7B"]["config"]["layer_types"])),
    ("SmallThinker-21BA3B-Instruct", "sliding_window_layout", [0] * 52),
    ("Solar-Open2-250B", "use_gqa_gate", False),  # a bool
    ("Olmo-Hybrid-7B", "linear_allow_neg_eigval", False),
    ("Kimi-K2.5", "scoring_func", "softmax"),  # a string the module holds
    ("granite-4.0-h-micro", "position_embedding_type", "rope"),
    ("granite-4.0-h-micro", "normalization_function", "layernorm"),  # ONLY, a string
    ("Kimi-K2.5", "hidden_act", "gelu"),  # a string run.py holds
    ("Kimi-K2.5", "rope_scaling.type", "linear"),
    ("Kimi-K2.5", "ep_size", 8),  # ONLY, a number
    ("Solar-Open2-250B", "partial_rotary_factor", 0.5),
    ("Olmo-Hybrid-7B", "rope_parameters.rope_theta", 10000),
    ("Solar-Open2-250B", "kda_use_full_proj", True),  # ONLY, a bool
    ("granite-4.0-h-micro", "mamba_d_state", 64),
    ("SmallThinker-21BA3B-Instruct", "moe_num_primary_experts", 32),
])
def test_a_value_that_is_not_the_programs_stops_the_run(name, path, value, tmp_path, monkeypatch):
    module = load_module(tmp_path, monkeypatch, TABLES[name])
    with pytest.raises(AssertionError) as err:
        bench_run.check_sizes(put(config_file(name), path, value), stub_program(name), module)
    assert f"{path}={value} in the file" in str(err.value)


@pytest.mark.parametrize("tables,says", [
    ({"HELD": {"hidden_size": "dim"}}, "HELD holds ['hidden_size']"),
    ({"ONLY": {"rope_scaling.factor": 1}}, "ONLY holds ['rope_scaling.factor']"),
    ({"STATED": {"torch_dtype": "the engine's dtype is the program's"}}, "STATED holds ['torch_dtype']"),
    ({"HELD": {"ep_size": "ep"}, "ONLY": {"ep_size": 1}}, "ONLY holds ['ep_size']"),
    ({"STATED": {"top_k": "  "}}, "STATED gives no reason for ['top_k']"),
])
def test_a_table_a_module_may_not_bring_is_refused_at_load(tables, says, tmp_path, monkeypatch):
    with pytest.raises(AssertionError) as err:
        load_module(tmp_path, monkeypatch, {"HELD": {}, "ONLY": {}, "STATED": {}, **tables})
    assert says in str(err.value)


# -- a cut is stated beside what was published ------------------------------------

SOLAR_CUT = {"num_hidden_layers": 4, "n_routed_experts": 40, "vocab_size": 24576, "gqa_layers": [0]}


def solar_cut(**changes) -> dict:
    """Solar-Open2-250B as one chip of eight that share each layer: one period
    of the layer pattern, 40 of the 320 experts, an eighth of the vocabulary."""
    row = ROWS["Solar-Open2-250B"]["config"]
    body = config_file("Solar-Open2-250B", **SOLAR_CUT, reduced=sorted(SOLAR_CUT),
                       published={k: row[k] for k in SOLAR_CUT})
    return dict(body, **changes)


def solar_cut_program():
    stub = stub_program("Solar-Open2-250B")
    stub.n_layers, stub.n_experts, stub.vocab_size, stub.gqa_layers = 4, 40, 24576, [0]
    stub.router_width = 320  # the router keeps the published width
    return stub


def solar_cut_tables() -> dict:
    tables = copy.deepcopy(TABLES["Solar-Open2-250B"])
    tables["HELD"]["published.n_routed_experts"] = "router_width"
    return tables


def test_depth_experts_and_vocabulary_cut_with_reduced_and_published_pass(tmp_path, monkeypatch):
    module = load_module(tmp_path, monkeypatch, solar_cut_tables())
    bench_run.check_sizes(solar_cut(), solar_cut_program(), module)
    assert check_source.differs(solar_cut(), ROWS["Solar-Open2-250B"]) == []
    # the module holds the published count too: a router cut to the experts held is refused
    narrow = solar_cut_program()
    narrow.router_width = 40
    with pytest.raises(AssertionError, match="published.n_routed_experts=320 in the file, 40 in the program"):
        bench_run.check_sizes(solar_cut(), narrow, module)


@pytest.mark.parametrize("changes,says", [
    ({"published": None}, "it lacks ['gqa_layers', 'n_routed_experts', 'num_hidden_layers', 'vocab_size']"),
    ({"published": {"num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608}},
     "it lacks ['gqa_layers']"),
    ({"reduced": ["gqa_layers", "n_routed_experts", "num_hidden_layers"]},
     "holds ['published.vocab_size'] beside them"),
    ({"reduced": []}, "holds ['published.gqa_layers', 'published.n_routed_experts'"),
])
def test_published_holds_exactly_the_paths_in_reduced(changes, says, tmp_path, monkeypatch):
    module = load_module(tmp_path, monkeypatch, solar_cut_tables())
    config = solar_cut(**changes)
    if config["published"] is None:
        del config["published"]
    with pytest.raises(AssertionError) as err:
        bench_run.check_sizes(config, solar_cut_program(), module)
    assert says in str(err.value)


def group_cut(**inner) -> dict:
    """The linear-attention group named in `reduced`, published whole."""
    row = ROWS["Solar-Open2-250B"]["config"]
    body = solar_cut(reduced=sorted([*SOLAR_CUT, "linear_attn_config"]),
                     published={**{k: row[k] for k in SOLAR_CUT},
                                "linear_attn_config": row["linear_attn_config"]})
    body["linear_attn_config"] = dict(row["linear_attn_config"], **inner)
    return body


@pytest.mark.parametrize("config,says", [
    # the cut with no `published`, and with a published value that is not the source's
    ({k: v for k, v in solar_cut().items() if k != "published"},
     "gives n_routed_experts as 40 and its source gives 320: reduced lists it, and "
     "published.n_routed_experts is nothing"),
    (solar_cut(published=dict(solar_cut()["published"], vocab_size=196000)),
     "published.vocab_size is 196000 where it has to be the source's value"),
    # a cut that `reduced` does not list
    (solar_cut(reduced=["num_hidden_layers", "n_routed_experts", "gqa_layers"]),
     "gives vocab_size as 24576 and its source gives 196608: reduced does not list it"),
    # a width, though `reduced` lists it and `published` states it: top level, and inside a group
    (solar_cut(moe_intermediate_size=640, reduced=sorted([*SOLAR_CUT, "moe_intermediate_size"]),
               published=dict(solar_cut()["published"], moe_intermediate_size=1280)),
     "gives moe_intermediate_size as 640 and its source gives 1280: a width may not change"),
    (solar_cut(num_experts_per_tok=4, reduced=sorted([*SOLAR_CUT, "num_experts_per_tok"]),
               published=dict(solar_cut()["published"], num_experts_per_tok=8)),
     "gives num_experts_per_tok as 4 and its source gives 8: a width may not change"),
    (group_cut(head_dim=64),
     "gives linear_attn_config.head_dim as 64 and its source gives 128: a width may not change"),
])
def test_check_source_refuses_a_cut_it_may_not_take(config, says):
    found = check_source.differs(config, ROWS["Solar-Open2-250B"])
    # one finding a path: with no `published` at all, each of the four cuts is one
    assert len(found) == (1 if "published" in config else 4), found
    assert any(says in line for line in found), found


def test_a_group_named_in_reduced_may_change_what_is_no_width():
    assert check_source.differs(group_cut(num_heads=8), ROWS["Solar-Open2-250B"]) == []


@pytest.mark.parametrize("name,path,value,says", [
    # PR 27's own fault, in the driver's words: null where the source gives 0
    ("Kimi-K2.5", "encoder_no_repeat_ngram_size", None,
     "gives encoder_no_repeat_ngram_size as null and its source gives 0"),
    ("Solar-Open2-250B", "linear_attn_config.num_kv_heads", 0,  # and 0 where it gives null
     "gives linear_attn_config.num_kv_heads as 0 and its source gives null"),
    ("Solar-Open2-250B", "use_rope", 0, "gives use_rope as 0 and its source gives false"),
    ("Kimi-K2.5", "rope_scaling", None, "leaves out rope_scaling.factor, which its source gives as 64"),
    ("Kimi-K2.5", "rope_scaling.type", "linear", 'gives rope_scaling.type as "linear" and its source gives "yarn"'),
    ("Olmo-Hybrid-7B", "layer_types", ROWS["Olmo-Hybrid-7B"]["config"]["layer_types"][:4],
     "gives layer_types as [\"linear_attention\""),
    ("Olmo-Hybrid-7B", "rope_parameters.rope_scaling", None,  # a key the source's group has not
     "gives rope_parameters.rope_scaling as null and its source gives nothing"),
])
def test_check_source_names_a_value_that_is_not_the_sources(name, path, value, says):
    found = check_source.differs(put(config_file(name), path, value), ROWS[name])
    assert found and all("reduced does not list it" in line for line in found)
    assert any(says in line for line in found), found


@pytest.mark.parametrize("name,path", [
    ("Kimi-K2.5", "ep_size"), ("granite-4.0-h-micro", "mamba_d_state"),
    ("Solar-Open2-250B", "linear_attn_config.num_kv_heads"), ("Solar-Open2-250B", "linear_attn_config"),
])
def test_check_source_names_a_key_left_out(name, path):
    found = check_source.differs(put(config_file(name), path, LEFT_OUT), ROWS[name])
    assert found and all(f"leaves out {path}" in line for line in found), found


def test_check_source_as_a_command(tmp_path):
    """Exit code 1 and PR 27's fault named on a catalog model's file; 0 on a
    file the catalog does not know (the two accepted files) and on a sound one."""
    def command(path):
        return subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "check_source.py"), str(path), FIXTURE],
            capture_output=True, text=True, timeout=60)

    pr27 = tmp_path / "kimi-k2.5.json"
    pr27.write_text(json.dumps(put(config_file("Kimi-K2.5"), "encoder_no_repeat_ngram_size", None)))
    out = command(pr27)
    assert out.returncode == 1
    assert "gives encoder_no_repeat_ngram_size as null and its source gives 0" in out.stdout
    pr27.write_text(json.dumps(config_file("Kimi-K2.5")))
    out = command(pr27)
    assert out.returncode == 0 and "holds every key of Kimi-K2.5's entry" in out.stdout
    for name in ("qwen3-8b-int8", "qwen3-embedding-8b-int8"):
        out = command(os.path.join(ROOT, "benchmark", "configs", name + ".json"))
        assert out.returncode == 0 and "not in the catalog" in out.stdout
