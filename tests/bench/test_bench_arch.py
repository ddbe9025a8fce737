"""A configuration file states its architecture, and the module it names
counts that architecture's costs: what the program is built from, the
cost count the readers use, and that a new architecture needs no edit to
the harness."""
import dataclasses

import pytest

from bench import check, deploy, readers
from bench.loop import LoopResult
from repro.configs.base import ModelConfig
from tests.bench import toy_arch

CONFIGS = ["olmo1b", "bloom176b-1blk"]
# the fields the harness passed on by a hand list before any file could
# state its architecture
HAND_LIST = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab_size", "pos_kind", "rope_theta", "norm_kind",
             "norm_eps", "tie_embeddings", "param_dtype", "act_dtype")


@pytest.mark.parametrize("name", CONFIGS)
def test_model_config_is_the_dense_one_built_by_hand(name):
    cfg = deploy.load_config(name)
    m = cfg["model"]
    want = ModelConfig(name=cfg["name"], family="dense", attn_kind="gqa",
                       max_seq_len=cfg["deployment"]["max_seq_len"],
                       **{k: m[k] for k in HAND_LIST if k in m})
    got = deploy.model_config(cfg)
    for f in dataclasses.fields(ModelConfig):
        assert getattr(got, f.name) == getattr(want, f.name), f.name


def test_a_file_states_latent_attention_and_experts():
    cfg = deploy.load_config("olmo1b")
    stated = dict(family="moe", attn_kind="mla", kv_lora_rank=512,
                  q_lora_rank=1536, rope_head_dim=64, n_experts=20,
                  n_shared_experts=2, moe_top_k=6, d_ff_expert=1536)
    cfg["model"].update(stated)
    mc = deploy.model_config(cfg)
    assert {k: getattr(mc, k) for k in stated} == stated
    assert mc.max_seq_len == cfg["deployment"]["max_seq_len"]


@pytest.mark.parametrize("key", ["kv_lora_rnak", "max_seq_len", "name"])
def test_a_model_key_the_program_does_not_take_raises(key):
    cfg = deploy.load_config("olmo1b")
    cfg["model"][key] = 512
    with pytest.raises(ValueError, match=key):
        deploy.model_config(cfg)


# (round_needs at positions 0, 17, 255 and 1000 over the deployment's hops
# and rows; token_model_flops at positions 0 and 767), as the harness
# counted them before the count moved into the configuration's module
@pytest.mark.parametrize("name,needs,flops", [
    ("olmo1b", (8757182464, 2315255808), (2353659904, 2454192128)),
    ("bloom176b-1blk", (19803176960, 5006131200), (5831712768, 5875695616)),
])
def test_the_cost_count_is_unchanged(name, needs, flops):
    cfg = deploy.load_config(name)
    ref, m, dep = check.reference_module(cfg), cfg["model"], \
        cfg["deployment"]
    assert ref.round_needs(m, [0, 17, 255, 1000], dep["servers"],
                           dep["rows"]) == needs
    assert (ref.token_model_flops(m, 0),
            ref.token_model_flops(m, 767)) == flops


class _Trace:
    def module_s(self, base):
        return {"jit_hop": 0.5}.get(base, 0.0)


def _toy_run():
    # two rounds in the stretch (0.5, 2.0) s, one after it
    loop = LoopResult(records=[], prefills=[],
                      spans=[("decode_round", 1.0, 1.2),
                             ("decode_round", 1.5, 1.8),
                             ("decode_round", 3.0, 3.1)],
                      rounds=[(1.0, [3, 5]), (1.5, [7]), (3.0, [1])])
    return readers.RunView(
        loop=loop, window=(0.5, 2.0),
        model={"flops_per_row": 1e12, "bytes_per_slot": 2e9},
        deployment={"servers": 2, "rows": 4, "chips": 1},
        peaks={"flops_bf16": 1e14, "hbm_bytes_per_s": 1e12},
        trace=_Trace(), arch=toy_arch)


@pytest.mark.parametrize("name,value", [
    # rounds need max(2e12 / 1e14, 16e9 / 1e12) = 0.020 s and
    # max(1e12 / 1e14, 16e9 / 1e12) = 0.016 s, over 0.5 s of jit_hop
    ("round_step_roofline", 7.2), ("round_step_roofline.batch", 7.2),
    # 3e12 + 15 FLOPs over 0.5 s of rounds at 1e14
    ("round_mfu", 6.0), ("round_mfu.batch", 6.0)])
def test_readers_count_with_the_run_s_architecture(name, value):
    assert readers.reader(name)(_toy_run()) == pytest.approx(value)
