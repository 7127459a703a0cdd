"""Logical-axis rules: divisibility degradation, mode overrides, cache axes."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch.specs import cache_logical_axes, cell_plan, input_specs
from repro.models import Model
from repro.sharding.rules import get_rules, logical_to_spec

MESH = AbstractMesh((2, 4, 8), ("pod", "data", "model"))


def test_basic_mapping():
    spec = logical_to_spec(("batch", None, "heads"), MESH, (64, 7, 16))
    assert spec == P(("pod", "data"), None, "model")


def test_divisibility_degradation():
    # 2 kv heads on an 8-way model axis -> dropped
    spec = logical_to_spec(("batch", None, "kv_heads", None), MESH, (64, 7, 2, 64))
    assert spec == P(("pod", "data"))
    # batch not divisible by pod*data=8 -> falls back to data-only? 12 % 8 != 0, 12 % 4 == 0
    spec = logical_to_spec(("batch",), MESH, (12,))
    assert spec == P("data")


def test_axis_never_reused():
    spec = logical_to_spec(("heads", "mlp"), MESH, (16, 32))
    # both map to model; only the first wins
    assert spec == P("model")


def test_image_axes_in_merged_table():
    """The merged default table resolves image logical axes (the primary
    workload) next to LM ones; the retired LM-only axes are gone."""
    rules = get_rules("serve")
    spec = logical_to_spec(("batch", "height", "width"), MESH, (64, 32, 32))
    # no row/col on the LM mesh: batch -> (pod, data), height -> model fallback
    assert spec == P(("pod", "data"), "model")
    for dead in ("seq", "expert_cap", "ssm_state", "conv_dim", "image_rows"):
        assert dead not in rules
        with pytest.raises(KeyError):
            logical_to_spec((dead,), MESH)
    image_only = get_rules("image")
    assert set(image_only) == {"batch", "height", "width", "channel"}


def test_train_rules_fsdp():
    rules = get_rules("train")
    spec = logical_to_spec(("embed", "mlp"), MESH, (64, 32), rules=rules)
    assert spec == P("data", "model")
    serve = logical_to_spec(("embed", "mlp"), MESH, (64, 32), rules=get_rules("serve"))
    assert serve == P(None, "model")


@pytest.mark.parametrize("arch", [a for a in list_archs() if a != "sobel-hd"])
def test_cache_axes_structure_matches_cache(arch):
    """cache_logical_axes must mirror Model.init_cache's tree structure."""
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(2, 8))
    axes = cache_logical_axes(cfg, model_axis_size=8)
    # must be zippable: same treedef when axes leaves are tuples
    jax.tree.map(
        lambda a, c: len(a) == len(c.shape) or (_ for _ in ()).throw(AssertionError((a, c.shape))),
        axes, cache,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(y, (str, type(None))) for y in x),
    )


def test_cell_plan_skips():
    glm = get_config("glm4-9b")
    plan = cell_plan(glm)
    assert plan["long_500k"][1] is not None        # skipped: full attention
    assert plan["train_4k"][1] is None
    mamba = get_config("falcon-mamba-7b")
    assert cell_plan(mamba)["long_500k"][1] is None  # runnable: sub-quadratic
    zamba = get_config("zamba2-2.7b")
    assert cell_plan(zamba)["long_500k"][1] is None


@pytest.mark.parametrize("arch", [a for a in list_archs() if a != "sobel-hd"])
def test_input_specs_shapes(arch):
    cfg = get_config(arch)
    specs = input_specs(cfg, "train_4k")
    assert specs["labels"].shape == (256, 4096)
    if cfg.family == "vlm":
        assert specs["tokens"].shape == (256, 4096 - cfg.num_patches)
        assert specs["patch_embeds"].shape == (256, cfg.num_patches, cfg.d_model)
    elif cfg.family == "encdec":
        assert specs["enc_embeds"].shape == (256, cfg.encoder_len, cfg.d_model)
    else:
        assert specs["tokens"].shape == (256, 4096)


def test_sobel_hd_specs():
    cfg = get_config("sobel-hd")
    specs = input_specs(cfg, "edge_2k")
    assert specs["images"].shape == (256, 2048, 2048)
