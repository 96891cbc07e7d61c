"""A configuration of another kind enters the benchmark through new files
alone: a kind module, a configuration file, a cell and a traffic mix, laid
out in a fresh root beside a copy of the benchmark's code, with no edit to
a file that exists. The kind here is an MLA + MoE decoder at the shapes of
the program's DeepSeek-V3 smoke preset."""
import json
import shutil

import pytest

import harness
from harness import BENCH

MLA_MOE = '''
"""MLA attention and routed experts with a shared one, after the leading
dense layers (DeepSeek-V3's config.json keys)."""


def model_config(cfg):
    from repro.configs.base import LayerSpec, MLAConfig, MoEConfig, ModelConfig

    dense = cfg["first_k_dense_replace"]
    return ModelConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_attention_heads"],
        head_dim=cfg["qk_nope_head_dim"], d_ff=cfg["moe_intermediate_size"],
        vocab=cfg["vocab_size"],
        layers=(LayerSpec(mixer="mla", ffn="dense",
                          d_ff=cfg["intermediate_size"]),) * dense
        + (LayerSpec(mixer="mla", ffn="moe"),) * (cfg["num_hidden_layers"] - dense),
        moe=MoEConfig(n_experts=cfg["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      n_shared=cfg["n_shared_experts"], router_aux_free=True,
                      capacity_factor=cfg["capacity_factor"]),
        mla=MLAConfig(q_lora_rank=cfg["q_lora_rank"],
                      kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_dim=cfg["qk_nope_head_dim"],
                      qk_rope_dim=cfg["qk_rope_head_dim"],
                      v_dim=cfg["v_head_dim"]),
        tie_embeddings=cfg["tie_word_embeddings"],
    )


def _attn_shapes(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return [(cfg["q_lora_rank"], d), (h * qk, cfg["q_lora_rank"]),
            (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], d),
            (h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]), cfg["kv_lora_rank"]),
            (d, h * cfg["v_head_dim"])]


def linear_shapes(cfg):
    """(out, in) of the linears one token runs through in an expert layer:
    attention, the shared expert and its routed experts."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    experts = cfg["n_shared_experts"] + cfg["num_experts_per_tok"]
    return _attn_shapes(cfg) + [(f, d), (f, d), (d, f)] * experts


def mpgemm_ops_per_token(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    dense = cfg["first_k_dense_replace"]
    attn = sum(2.0 * m * k for m, k in _attn_shapes(cfg))
    mlp = 2.0 * 3 * d * f
    moe = sum(2.0 * m * k for m, k in linear_shapes(cfg))
    return dense * (attn + mlp) + (cfg["num_hidden_layers"] - dense) * moe


def attention_ops(cfg, keys):
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (2.0 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"]) * keys
            * cfg["num_hidden_layers"])


def head_ops(cfg):
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
'''

CONFIG = {
    "name": "deepseek-smoke", "kind": "mla_moe", "reference": "mla_moe",
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 160, "moe_intermediate_size": 96,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "capacity_factor": 4.0, "vocab_size": 512, "tie_word_embeddings": False,
}


@pytest.fixture
def root(tmp_path):
    """A copy of the benchmark with one more configuration, of a new kind,
    added as new files only."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "deepseek-smoke", "source": "test",
                             "file": "bench/configs/deepseek-smoke.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "deepseek-smoke.agent_prefix",
                               "config": "deepseek-smoke",
                               "traffic": "agent_prefix", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    new = {
        "bench/kinds/mla_moe.py": MLA_MOE,
        "bench/configs/deepseek-smoke.json": json.dumps(CONFIG),
        "bench/cells/deepseek-smoke.agent_prefix.json": json.dumps({
            "engine": {"max_slots": 2, "max_len": 128, "prefill_chunk": 16,
                       "page_size": 16, "n_pages": 17},
            "check": {"tokens": 8, "min_tokens": 4, "min_requests": 1,
                      "max_requests": 2, "gap_limit": 1.0}}),
    }
    for rel, text in new.items():
        assert not (tmp_path / rel).exists()
        (tmp_path / rel).write_text(text)
    yield tmp_path
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert {k: v for k, v in after.items() if k in before} == before


def test_new_kind_loads_with_new_files_only(root):
    from repro.configs.deepseek_v3_671b import SMOKE

    cell = harness.load_cell("deepseek-smoke.agent_prefix", root=root)
    assert cell.kind.__name__ == "kind_mla_moe"
    assert cell.engine["n_pages"] == 17
    assert cell.mix["shared"]["prefixes"] == 2
    mc = cell.kind.model_config(cell.config)
    for f in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "layers", "moe", "mla", "tie_embeddings"):
        assert getattr(mc, f) == getattr(SMOKE, f), f
    per_token = cell.kind.mpgemm_ops_per_token(cell.config)
    # 1 dense layer + 2 expert layers of MLA linears (48x64, 96x48, 40x64,
    # 128x32, 64x64) and MLPs (3 x 64x160 dense; 3 experts of 3 x 64x96)
    attn = 2 * (48 * 64 + 96 * 48 + 40 * 64 + 128 * 32 + 64 * 64)
    assert per_token == 3 * attn + 2 * 3 * 64 * 160 + 2 * 2 * 3 * 3 * 64 * 96
    assert cell.kind.attention_ops(cell.config, 10) == 2 * 4 * 40 * 10 * 3
    assert cell.kind.head_ops(cell.config) == 2 * 64 * 512
    # the cell's per-layer metrics reach it through the lists it is named in
    assert {m["name"] for m in cell.per_layer} <= {
        m["name"] for m in json.loads((root / "BENCHMARK.json").read_text())["per_layer"]}


def test_kind_without_its_file_is_refused(root):
    (root / "bench" / "kinds" / "mla_moe.py").unlink()
    with pytest.raises(FileNotFoundError, match="mla_moe.py"):
        harness.load_cell("deepseek-smoke.agent_prefix", root=root)
