"""Config-system tests (analog of src/tests/config_parsing.cu)."""
import json

import pytest

from amgx_tpu.config import Config
from amgx_tpu.errors import AMGXError


def test_defaults():
    cfg = Config()
    assert cfg.get("max_iters") == 100
    assert cfg.get("tolerance") == 1e-12
    assert cfg.get("solver") == "AMG"
    assert cfg.get("cycle") == "V"


def test_flat_string():
    cfg = Config.from_string(
        "max_iters=42, tolerance=1e-8; monitor_residual=1")
    assert cfg.get("max_iters") == 42
    assert cfg.get("tolerance") == 1e-8
    assert cfg.get("monitor_residual") == 1


def test_scoped_string():
    cfg = Config.from_string(
        "solver(amg)=AMG, amg:presweeps=2, amg:max_iters=1, max_iters=50")
    assert cfg.get("solver") == "AMG"
    assert cfg.get_scope("solver") == "amg"
    assert cfg.get("presweeps", "amg") == 2
    assert cfg.get("max_iters", "amg") == 1
    assert cfg.get("max_iters") == 50
    # fallback: unset in scope -> default scope
    assert cfg.get("postsweeps", "amg") == 1


def test_json_v2_nested_scopes():
    obj = {
        "config_version": 2,
        "solver": {
            "scope": "main",
            "solver": "FGMRES",
            "max_iters": 100,
            "gmres_n_restart": 10,
            "preconditioner": {
                "scope": "amg",
                "solver": "AMG",
                "algorithm": "AGGREGATION",
                "selector": "SIZE_2",
                "max_iters": 1,
                "smoother": "MULTICOLOR_DILU",
            },
        },
    }
    cfg = Config.from_dict(obj)
    name, scope = cfg.get_solver("solver")
    assert (name, scope) == ("FGMRES", "main")
    assert cfg.get("max_iters", "main") == 100
    pname, pscope = cfg.get_solver("preconditioner", "main")
    assert (pname, pscope) == ("AMG", "amg")
    assert cfg.get("selector", "amg") == "SIZE_2"
    assert cfg.get("max_iters", "amg") == 1
    assert cfg.get("algorithm", "amg") == "AGGREGATION"


def test_reference_config_file_parses(tmp_path):
    # shipped-config shape (mirrors src/configs/FGMRES_AGGREGATION.json)
    obj = {
        "config_version": 2,
        "solver": {
            "preconditioner": {
                "error_scaling": 0,
                "algorithm": "AGGREGATION",
                "solver": "AMG",
                "smoother": "MULTICOLOR_DILU",
                "presweeps": 0,
                "selector": "SIZE_2",
                "coarse_solver": "DENSE_LU_SOLVER",
                "max_iters": 1,
                "postsweeps": 3,
                "min_coarse_rows": 32,
                "relaxation_factor": 0.75,
                "scope": "amg",
                "max_levels": 50,
                "cycle": "V",
            },
            "use_scalar_norm": 1,
            "solver": "FGMRES",
            "max_iters": 100,
            "monitor_residual": 1,
            "gmres_n_restart": 10,
            "convergence": "RELATIVE_INI",
            "scope": "main",
            "tolerance": 1e-06,
            "norm": "L2",
        },
    }
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps(obj))
    cfg = Config.from_file(str(p))
    assert cfg.get_solver("solver") == ("FGMRES", "main")
    assert cfg.get_solver("coarse_solver", "amg") == ("DENSE_LU_SOLVER",
                                                      "default")
    assert cfg.get("relaxation_factor", "amg") == 0.75
    assert cfg.get("norm", "main") == "L2"


def test_validation_errors():
    with pytest.raises(AMGXError):
        Config.from_string("no_such_param=3")
    with pytest.raises(AMGXError):
        Config.from_string("cycle=Q")
    with pytest.raises(AMGXError):
        Config.from_string("relaxation_factor=5.0")  # above max 2.0
    with pytest.raises(AMGXError):
        # non-solver param cannot open a scope
        Config.from_string("max_iters(foo)=3")


@pytest.mark.parametrize("key", ["cycle_fusion",
                                 "cycle_fusion_tail_rows"])
def test_removed_cycle_fusion_keys_are_unknown(key):
    """The knobs of the flat-gather family left the registry with it: a
    configuration that still names one is told so, in a string, in the
    cycle's scope and from the registry's own getter, like any other
    unknown key (`dist_cycle_fusion` is another knob and stays)."""
    from amgx_tpu.config import parameter_registry
    assert key not in parameter_registry()
    assert len(parameter_registry()) == 215
    for text in (f"{key}=0", f"solver(amg)=AMG, amg:{key}=0"):
        with pytest.raises(AMGXError, match="unknown parameter"):
            Config.from_string(text)
    with pytest.raises(AMGXError, match="unknown parameter"):
        Config().get(key)
    assert Config.from_string("dist_cycle_fusion=0").get(
        "dist_cycle_fusion") == 0


def test_case_tolerant_enums():
    cfg = Config.from_string("norm=l2")
    assert cfg.get("norm") == "L2"
