"""A configuration file reaches the program only as it states it: every key
is carried into ``ModelConfig``, held at the value the program fixes, or
descriptive; anything else is refused, before any weight is drawn."""
import dataclasses
import time

import pytest

from bench import model
from bench.traffic import load_mix

# Mistral-Large-Instruct-2407's config.json (88 layers, d 12288, GQA 96/8,
# untied head, RMSNorm eps 1e-5), with the benchmark's own keys
MISTRAL = {
    "name": "mistral-large-123b",
    "source": "https://huggingface.co/mistralai/Mistral-Large-Instruct-2407/"
              "blob/main/config.json",
    "reference": "reference.py", "reduced": [], "check": {"logit_gap": 0.3},
    "architectures": ["MistralForCausalLM"], "attention_dropout": 0.0,
    "bos_token_id": 1, "eos_token_id": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 12288, "initializer_range": 0.02,
    "intermediate_size": 28672, "max_position_embeddings": 131072,
    "model_type": "mistral", "num_attention_heads": 96,
    "num_hidden_layers": 88, "num_key_value_heads": 8, "rms_norm_eps": 1e-05,
    "rope_theta": 1000000.0, "sliding_window": None,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "use_cache": True, "vocab_size": 32768}


def parent_model_config(cfg: dict):
    """``model_config`` as it stood before the key lists, for comparison."""
    from repro.models.transformer import ModelConfig
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_block=(("attn", "dense"),), qk_norm=bool(cfg.get("qk_norm")),
        rope_theta=float(cfg["rope_theta"]), mlp_kind="swiglu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"], source=cfg["source"])


def stand_in():
    """The program's ``ModelConfig`` with a ``norm_eps`` field declared."""
    from repro.models.transformer import ModelConfig

    @dataclasses.dataclass(frozen=True)
    class WithNormEps(ModelConfig):
        norm_eps: float = 1e-6
    return WithNormEps


@pytest.fixture
def with_norm_eps(monkeypatch):
    from repro.models import transformer
    cls = stand_in()
    monkeypatch.setattr(transformer, "ModelConfig", cls)
    return cls


@pytest.mark.parametrize("traffic", ["docqa-8k", "chat-poisson"])
def test_qwen3_model_config_is_the_parents(traffic):
    cfg = model.load_config("qwen3-1.7b")
    got = model.model_config(cfg, load_mix(traffic)["engine"]["max_len"])
    want = parent_model_config(cfg)
    for f in dataclasses.fields(want):
        a, b = getattr(got, f.name), getattr(want, f.name)
        assert (a, type(a)) == (b, type(b)), f.name
    assert got == want and hash(got) == hash(want)


def test_every_key_of_qwen3_is_in_exactly_one_list():
    from repro.models.transformer import ModelConfig
    carried, held, descriptive = model.key_lists(ModelConfig)
    for k in model.load_config("qwen3-1.7b"):
        assert (k in carried) + (k in held) + (k in descriptive) == 1, k


def test_lists_are_disjoint_with_and_without_the_field():
    from repro.models.transformer import ModelConfig
    for cls, eps_in in ((ModelConfig, "held"), (stand_in(), "carried")):
        carried, held, descriptive = model.key_lists(cls)
        assert not (set(carried) & set(held) or set(carried) & descriptive
                    or set(held) & descriptive)
        assert set(carried) | set(held) >= set(model.CARRIED)
        assert "rms_norm_eps" in {"held": held, "carried": carried}[eps_in]


def test_mistral_eps_is_refused():
    with pytest.raises(ValueError, match="rms_norm_eps"):
        model.model_config(MISTRAL)
    # nothing else of the file stands in the way
    mc = model.model_config(dict(MISTRAL, rms_norm_eps=1e-6), 9216)
    assert (mc.n_layers, mc.d_model, mc.n_heads, mc.n_kv_heads, mc.d_ff,
            mc.vocab, mc.tie_embeddings, mc.qk_norm) == (
        88, 12288, 96, 8, 28672, 32768, False, False)


DROP = object()


@pytest.mark.parametrize("key,value,max_len", [
    ("rope_type_of_my_own", "yarn", None),    # in no list
    ("attention_bias", True, None),
    ("mlp_bias", True, None),
    ("hidden_act", "gelu", None),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, None),
    ("sliding_window", 4096, None),
    ("use_sliding_window", True, None),
    ("head_dim", DROP, None),                 # a carried key left out
    ("max_position_embeddings", 8192, 9216),  # the engine's max_len beyond it
])
def test_refused_naming_the_key(key, value, max_len):
    cfg = dict(model.load_config("qwen3-1.7b"))
    if value is DROP:
        del cfg[key]
    else:
        cfg[key] = value
    with pytest.raises(ValueError, match=key):
        model.model_config(cfg, max_len)


def test_norm_eps_is_carried_where_the_program_declares_it(with_norm_eps):
    assert model.model_config(MISTRAL).norm_eps == 1e-5
    qwen = model.model_config(model.load_config("qwen3-1.7b"))
    assert isinstance(qwen, with_norm_eps) and qwen.norm_eps == 1e-6


def test_run_refuses_before_any_weight_is_drawn(monkeypatch):
    from bench.run import run_cell

    def drawn(*a, **kw):
        raise AssertionError("weights drawn")

    monkeypatch.setattr(model, "program_params", drawn)
    bench = {"workloads": [{"name": "x", "config": "qwen3-1.7b",
                            "traffic": "docqa-8k", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    cfg = dict(model.load_config("qwen3-1.7b"), rms_norm_eps=1e-5)
    with pytest.raises(ValueError, match="rms_norm_eps"):
        run_cell(bench, "x", 2 ** 31 + 5, 1.0, False, cfg=cfg,
                 t_process=time.monotonic())
