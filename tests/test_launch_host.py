"""The local launch path: which chip the plans are priced for, where the
compile cache lives, and the refusal of a plan that does not fit."""
import dataclasses

import jax
import pytest

from repro.configs import SHAPES, get_config
from repro.core.cluster import (CPU_HOST, TPU_V5E, TPU_V5P, TPU_V6E,
                                chip_for_device_kind, cpu_host_config,
                                local_cluster_config)
from repro.launch import compile_cache, train
from repro.launch.mesh import host_mesh_shape, make_host_mesh


@pytest.mark.parametrize("kind,chip", [
    ("TPU v5 lite", TPU_V5E), ("TPU v5", TPU_V5P), ("TPU v6 lite", TPU_V6E),
    ("cpu", CPU_HOST)])
def test_device_kind_maps_to_chip(kind, chip):
    assert chip_for_device_kind(kind) is chip


@pytest.mark.parametrize("kind", ["TPU v4", "NVIDIA H100 80GB HBM3", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError, match="unknown device kind"):
        chip_for_device_kind(kind)


def test_local_cluster_config_prices_the_detected_chip():
    cc = local_cluster_config("TPU v5 lite", (2, 2), ("data", "model"))
    assert cc.chip is TPU_V5E
    assert (cc.mesh_shape, cc.mesh_axes) == ((2, 2), ("data", "model"))
    assert local_cluster_config("cpu", (1,), ("data",)) == cpu_host_config()


def test_host_mesh_shape():
    assert host_mesh_shape(1) == ((1,), ("data",))
    assert host_mesh_shape(4) == ((2, 2), ("data", "model"))
    assert host_mesh_shape(8) == ((8,), ("data",))


def test_mesh_cluster_reads_the_devices():
    cc = train.mesh_cluster(make_host_mesh())
    assert cc.chip is CPU_HOST
    assert cc.num_chips == len(jax.devices())


def test_compile_cache_dir_from_environment(tmp_path):
    env = {compile_cache.ENV_VAR: str(tmp_path)}
    assert compile_cache.compile_cache_dir(env) == str(tmp_path)


def test_compile_cache_dir_defaults_to_the_checkout():
    assert compile_cache.compile_cache_dir({}) == str(
        compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()


def test_enable_compile_cache_sets_no_other_dir(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        jax.config.update("jax_compilation_cache_dir", prev)
        # set from outside: JAX reads the variable itself, nothing is set
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_rank_plans_refuses_an_infeasible_winner():
    shape = dataclasses.replace(SHAPES["train_4k"], global_batch=8,
                                seq_len=2048)
    cc = local_cluster_config("TPU v5 lite", (1,), ("data",))
    with pytest.raises(train.InfeasiblePlan,
                       match=r"best plan dp\+tp\[.*\] needs estimate_hbm "
                             r"[0-9.]+ GB .* tpu_v5e"):
        train.rank_plans(get_config("qwen1.5-0.5b"), shape, cc)


def test_train_main_exits_before_building_an_infeasible_plan(
        monkeypatch, tmp_path, capsys):
    def no_trainer(*args, **kwargs):
        raise AssertionError("an infeasible plan reached the Trainer")

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(train, "Trainer", no_trainer)
    with pytest.raises(SystemExit) as exit_info:
        train.main(["--arch", "qwen1.5-110b", "--global-batch", "8",
                    "--seq-len", "4096", "--steps", "1"])
    assert exit_info.value.code == 2
    err = capsys.readouterr().err
    assert "refusing to train" in err and "estimate_hbm" in err
