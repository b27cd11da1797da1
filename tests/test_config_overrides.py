"""CLI --set override parsing (config.parse_cli_overrides) and the
bool-field guard in the dotted-override machinery."""

import pytest

from mx_rcnn_tpu.config import generate_config, parse_cli_overrides


def test_literals_bools_and_strings_parse():
    out = parse_cli_overrides([
        "train.batch_images=2",
        "train.lr=0.02",
        "image.pad_shape=(128,128)",
        "network.tensor_parallel=true",
        "network.use_mask=FALSE",
        "network.remat=off",
        "network.norm=group",
    ])
    assert out["train.batch_images"] == 2
    assert out["train.lr"] == 0.02
    assert out["image.pad_shape"] == (128, 128)
    assert out["network.tensor_parallel"] is True
    assert out["network.use_mask"] is False
    assert out["network.remat"] is False
    assert out["network.norm"] == "group"


def test_malformed_pair_raises():
    with pytest.raises(ValueError, match="KEY=VALUE"):
        parse_cli_overrides(["train.lr"])


def test_cli_bools_reach_config():
    cfg = generate_config(
        "resnet50", "synthetic",
        **parse_cli_overrides(["network.tensor_parallel=true"]))
    assert cfg.network.tensor_parallel is True


def test_string_on_bool_field_rejected():
    # A stray string must never land on a bool field (a truthy "false"
    # would silently ENABLE the feature it was meant to disable).
    with pytest.raises(ValueError, match="bool"):
        generate_config("resnet50", "synthetic",
                        **{"network.tensor_parallel": "maybe"})


@pytest.mark.parametrize("key", ["train.flat_params",
                                 "train.multi_step_dispatch"])
def test_removed_options_are_refused(key):
    """An option that no longer exists fails like any unknown key, naming
    the field: it must not be swallowed and leave the run believing it
    set something."""
    with pytest.raises(TypeError, match=key.split(".")[1]):
        generate_config("resnet50", "synthetic",
                        **parse_cli_overrides([f"{key}=1"]))
