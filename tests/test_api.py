"""Public API tests: HetaConfig validation + round-trips, session stage
ordering, executor registry, and cross-executor loss parity through the
uniform protocol (ISSUE 1 acceptance)."""

import argparse

import numpy as np
import pytest

from repro.api import (
    CacheConfig,
    DataConfig,
    Heta,
    HetaConfig,
    HetaStageError,
    ModelConfig,
    PartitionConfig,
    PipelineConfig,
    RunConfig,
    add_config_args,
    config_from_args,
    executors,
)
from repro.launch.train import train_hgnn


def tiny_config(executor="raf_spmd", **run_kw):
    return HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                        batch_size=16),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=32),
        cache=CacheConfig(cache_mb=2),
        run=RunConfig(executor=executor, steps=3, lr=1e-2, seed=0, **run_kw),
    )


# --------------------------------------------------------------------------
# config validation + round-trips
# --------------------------------------------------------------------------


def test_config_validation():
    with pytest.raises(ValueError, match="model"):
        ModelConfig(model="gcn")
    with pytest.raises(ValueError, match="placement"):
        PartitionConfig(placement="randomly")
    with pytest.raises(ValueError, match="fanouts"):
        DataConfig(fanouts=())
    with pytest.raises(ValueError, match="mesh_shape"):
        RunConfig(mesh_shape=(0, 1))
    with pytest.raises(ValueError, match="policy"):
        CacheConfig(policy="lru")
    with pytest.raises(ValueError, match="divisible"):
        ModelConfig(hidden=30, num_heads=4)


def test_config_defaults_match_legacy_train_hgnn():
    """The config tree's defaults ARE the legacy kwargs-blob defaults."""
    cfg = HetaConfig()
    assert cfg.data.dataset == "ogbn-mag" and cfg.data.fanouts == (4, 3)
    assert cfg.partition.num_partitions == 4 and cfg.partition.placement == "meta"
    assert cfg.run.steps == 20 and cfg.run.lr == 5e-3
    assert cfg.cache.cache_mb == 4 and not cfg.cache.hotness_only


def test_flat_kwargs_round_trip():
    cfg = HetaConfig.from_flat_kwargs(
        dataset="freebase", scale=0.001, model="rgat", num_partitions=3,
        mesh_shape=(1, 2), batch_size=8, fanouts=(3, 2), hidden=32, steps=4,
        lr=1e-2, cache_mb=2, hotness_only=True, naive_placement=True,
        learnable_dim=16, seed=3, log_every=2, executor="raf",
    )
    assert cfg.partition.placement == "naive"
    assert cfg.cache.policy == "hotness"
    assert cfg.data.fanouts == (3, 2) and cfg.run.mesh_shape == (1, 2)
    assert HetaConfig.from_flat_kwargs(**cfg.to_flat_kwargs()) == cfg
    with pytest.raises(TypeError, match="unknown train_hgnn kwarg"):
        HetaConfig.from_flat_kwargs(batchsize=8)


def test_dict_round_trip():
    cfg = tiny_config("raf")
    d = cfg.to_dict()
    assert d["run"]["executor"] == "raf"
    assert isinstance(d["data"]["fanouts"], list)  # JSON-friendly
    assert HetaConfig.from_dict(d) == cfg
    with pytest.raises(TypeError, match="unknown"):
        HetaConfig.from_dict({"data": {"nope": 1}})


def test_cli_round_trip():
    """CLI flags are derived from the config fields; parsing them back
    reproduces the config."""
    ap = argparse.ArgumentParser()
    add_config_args(ap)
    args = ap.parse_args([
        "--dataset", "freebase", "--fanouts", "3,2", "--mesh", "1x2",
        "--partitions", "3", "--placement", "naive", "--hidden", "32",
        "--cache-policy", "hotness", "--executor", "raf", "--steps", "4",
    ])
    cfg = config_from_args(args)
    assert cfg.data.dataset == "freebase" and cfg.data.fanouts == (3, 2)
    assert cfg.run.mesh_shape == (1, 2) and cfg.run.executor == "raf"
    assert cfg.partition.num_partitions == 3
    assert cfg.partition.placement == "naive"
    assert cfg.cache.policy == "hotness" and cfg.run.steps == 4
    # unset flags keep defaults
    assert cfg.data.batch_size == DataConfig().batch_size


def test_updated_rejects_unknown_sections_and_fields():
    cfg = HetaConfig()
    with pytest.raises(TypeError):
        cfg.updated(runn=dict(steps=2))
    with pytest.raises(TypeError):
        cfg.updated(run=dict(stepss=2))
    assert cfg.with_executor("raf").run.executor == "raf"


# --------------------------------------------------------------------------
# executor registry
# --------------------------------------------------------------------------


def test_registry_lookup():
    assert set(executors.available()) >= {"vanilla", "raf", "raf_spmd"}
    for name in ("vanilla", "raf", "raf_spmd"):
        assert executors.get(name).name == name
    with pytest.raises(KeyError, match="raf_spmd"):  # lists what IS available
        executors.get("bogus_executor")


def test_register_custom_executor():
    @executors.register("_test_dummy")
    class Dummy(executors.Executor):
        pass

    try:
        assert "_test_dummy" in executors.available()
        assert isinstance(executors.get("_test_dummy"), Dummy)
    finally:
        del executors._REGISTRY["_test_dummy"]


# --------------------------------------------------------------------------
# session lifecycle
# --------------------------------------------------------------------------


def test_stage_ordering_errors():
    sess = Heta(tiny_config())
    with pytest.raises(HetaStageError, match="compile"):
        sess.fit()
    with pytest.raises(HetaStageError, match="build_graph"):
        sess.partition()
    sess.build_graph()
    with pytest.raises(HetaStageError, match="profile_and_cache"):
        sess.compile()
    part = sess.partition()
    assert part.meta_local and part.num_partitions == 2
    with pytest.raises(HetaStageError, match="compile"):
        sess.step()


def test_stagewise_equals_run():
    """Stage-by-stage execution and the run() convenience are equivalent."""
    sess = Heta(tiny_config())
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    m1 = sess.fit()
    m2 = Heta(tiny_config()).run()
    np.testing.assert_allclose(m1["losses"], m2["losses"], rtol=0, atol=0)


def test_unknown_executor_at_compile():
    sess = Heta(tiny_config(executor="not_an_executor"))
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    with pytest.raises(KeyError, match="available"):
        sess.compile()


def test_partition_report_comm_accounting():
    sess = Heta(tiny_config())
    sess.build_graph()
    part = sess.partition()
    comm = sess.comm_report(bytes_per_elem=2)
    # meta placement: exactly the Θ(B·hidden) root exchange (Prop 2)
    assert comm["raf_meta"] == part.raf_bytes(16, 32, 2)
    assert comm["raf_meta"] <= comm["raf_naive"]


def test_evaluate_no_update():
    sess = Heta(tiny_config("vanilla"))
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    e1 = sess.evaluate()
    e2 = sess.evaluate()
    assert np.isfinite(e1["loss"]) and e1["loss"] == e2["loss"]  # no training
    assert sess.losses == []


# --------------------------------------------------------------------------
# executor parity through the uniform protocol (acceptance criteria)
# --------------------------------------------------------------------------


def _losses(executor):
    return np.asarray(Heta(tiny_config(executor)).run()["losses"])


def test_parity_vanilla_vs_raf():
    """Prop 1, trained: the simulated RAF executor follows the vanilla loss
    curve step-for-step (identical seeds -> identical params and batches)."""
    lv, lr_ = _losses("vanilla"), _losses("raf")
    np.testing.assert_allclose(lv, lr_, atol=1e-5)


def test_parity_raf_vs_raf_spmd():
    """The production SPMD executor trains the same model as the simulated
    one (stacked/padded representation + sparse cache updates)."""
    lr_, ls = _losses("raf"), _losses("raf_spmd")
    assert np.all(np.isfinite(ls))
    np.testing.assert_allclose(lr_, ls, atol=5e-3)


# --------------------------------------------------------------------------
# the deprecated wrapper
# --------------------------------------------------------------------------


def test_train_hgnn_wrapper_result_keys():
    m = train_hgnn(dataset="ogbn-mag", scale=0.002, model="rgcn",
                   num_partitions=2, batch_size=16, fanouts=(3, 2), steps=2,
                   cache_mb=2)
    for key in ("losses", "step_time_s", "hit_rates", "partitioning",
                "meta_local", "cache_allocation"):
        assert key in m, key
    assert len(m["losses"]) == 2 and m["meta_local"]


# --------------------------------------------------------------------------
# async host pipeline (ISSUE 2 acceptance): parity with the serial path
# --------------------------------------------------------------------------


def _pipe_config(executor, train_learnable=True, **pipeline):
    """``tiny_config(executor)`` under the "stale" snapshot policy unless
    ``pipeline`` says otherwise."""
    cfg = tiny_config(executor)
    if not train_learnable:
        cfg = cfg.updated(model=dict(train_learnable=False))
    return cfg.updated(pipeline={"snapshot": "stale", **pipeline})


@pytest.mark.parametrize("executor", ["vanilla", "raf", "raf_spmd"])
def test_pipeline_parity_frozen_features(executor):
    """With frozen feature tables, staging is time-invariant: the "stale"
    and "fresh" snapshot policies must produce bit-identical losses for
    every executor."""
    off = Heta(tiny_config(executor).updated(
        model=dict(train_learnable=False))).run()
    on = Heta(_pipe_config(executor, train_learnable=False)).run()
    assert off["losses"] == on["losses"]  # bit-identical
    assert on["sampler_workers"] == off["sampler_workers"] == 0
    assert "overlap_fraction" in on and on["overlap_fraction"] >= 0.0


@pytest.mark.parametrize("executor", ["vanilla", "raf"])
def test_pipeline_parity_learnable_dense_executors(executor):
    """Dense executors carry learnable rows in the parameter bundle — their
    staging never reads tables, so even learnable training is bit-exact."""
    off = Heta(tiny_config(executor)).run()
    on = Heta(_pipe_config(executor)).run()
    assert off["losses"] == on["losses"]


def test_pipeline_learnable_spmd_stale_within_tolerance():
    """raf_spmd staging snapshots learnable tables; under the "stale"
    policy background staging may lag by <= depth+1 steps, so losses track
    the serial path within optimization noise."""
    off = Heta(tiny_config("raf_spmd")).run()
    on = Heta(_pipe_config("raf_spmd")).run()
    np.testing.assert_allclose(off["losses"], on["losses"], atol=5e-2)


def test_kernel_config_round_trips():
    cfg = HetaConfig().updated(kernels=dict(enabled=False, stacked_agg=False,
                                            interpret=True))
    assert HetaConfig.from_dict(cfg.to_dict()) == cfg
    assert HetaConfig.from_flat_kwargs(**cfg.to_flat_kwargs()) == cfg
    with pytest.raises(ValueError, match="kernels.enabled"):
        HetaConfig().updated(kernels=dict(enabled="yes"))
    with pytest.raises(ValueError, match="interpret"):
        HetaConfig().updated(kernels=dict(interpret="auto"))
    # derived CLI flags (tri-state interpret: absent -> None)
    ap = argparse.ArgumentParser()
    add_config_args(ap)
    got = config_from_args(ap.parse_args(
        ["--no-kernels", "--kernel-interpret", "--no-kernel-gather"]))
    assert not got.kernels.enabled and got.kernels.interpret is True
    assert not got.kernels.gather and got.kernels.stacked_agg
    assert config_from_args(ap.parse_args([])).kernels.interpret is None


def test_kernel_block_config_round_trips():
    """The block-size knobs (autotune + explicit overrides) round-trip
    through dict / flat-kwargs / CLI, and non-positive blocks are rejected."""
    cfg = HetaConfig().updated(kernels=dict(autotune=True, block_n=64,
                                            block_in=256, fuse_epilogue=False))
    assert HetaConfig.from_dict(cfg.to_dict()) == cfg
    flat = cfg.to_flat_kwargs()
    assert flat["kernel_autotune"] is True
    assert flat["kernel_block_n"] == 64 and flat["kernel_block_out"] is None
    assert HetaConfig.from_flat_kwargs(**flat) == cfg
    for f in ("block_n", "block_out", "block_in"):
        for bad in (0, -8, 1.5, True):
            with pytest.raises(ValueError, match=f"kernels.{f}"):
                HetaConfig().updated(kernels={f: bad})
    with pytest.raises(ValueError, match="kernels.autotune"):
        HetaConfig().updated(kernels=dict(autotune="yes"))
    with pytest.raises(ValueError, match="kernels.fuse_epilogue"):
        HetaConfig().updated(kernels=dict(fuse_epilogue=1.0))
    # derived CLI flags (unset blocks stay None -> dispatch defaults)
    ap = argparse.ArgumentParser()
    add_config_args(ap)
    got = config_from_args(ap.parse_args(
        ["--kernel-autotune", "--kernel-block-n", "256",
         "--no-kernel-fuse-epilogue"]))
    assert got.kernels.autotune and got.kernels.block_n == 256
    assert got.kernels.fuse_epilogue is False
    base = config_from_args(ap.parse_args([]))
    assert base.kernels.block_n is None and base.kernels.autotune is False
    assert base.kernels.fuse_epilogue is True


def test_cache_readmit_config_round_trips():
    cfg = HetaConfig().updated(cache=dict(readmit_every=2),
                               serve=dict(readmit_every=5))
    assert HetaConfig.from_dict(cfg.to_dict()) == cfg
    assert HetaConfig.from_flat_kwargs(**cfg.to_flat_kwargs()) == cfg
    with pytest.raises(ValueError, match="readmit_every"):
        HetaConfig().updated(cache=dict(readmit_every=-1))
    with pytest.raises(ValueError, match="readmit_every"):
        HetaConfig().updated(serve=dict(readmit_every=-2))
    ap = argparse.ArgumentParser()
    add_config_args(ap)
    got = config_from_args(ap.parse_args(
        ["--readmit-every", "3", "--serve-readmit-every", "7"]))
    assert got.cache.readmit_every == 3 and got.serve.readmit_every == 7
    assert config_from_args(ap.parse_args([])).cache.readmit_every == 0


def test_fit_loop_triggers_online_readmission():
    """cache.readmit_every wires EmbedEngine.rebalance into the fit loop:
    4 steps at period 2 -> exactly 2 rebalances, and training still runs."""
    sess = Heta(tiny_config().updated(cache=dict(readmit_every=2),
                                      run=dict(steps=4)))
    m = sess.run()
    assert sess.engine.rebalances == 2
    assert sess.engine.stats()["rebalances"] == 2
    assert len(m["losses"]) == 4
    assert np.isfinite(m["losses"]).all()


def test_pipeline_config_round_trips():
    assert HetaConfig().pipeline == PipelineConfig(depth=2, snapshot="fresh",
                                                   num_workers=0)
    cfg = HetaConfig().updated(pipeline=dict(depth=3, snapshot="stale",
                                             num_workers=4))
    assert HetaConfig.from_dict(cfg.to_dict()) == cfg
    assert HetaConfig.from_flat_kwargs(**cfg.to_flat_kwargs()) == cfg
    with pytest.raises(ValueError, match="snapshot"):
        HetaConfig().updated(pipeline=dict(snapshot="psychic"))
    with pytest.raises(ValueError, match="depth"):
        HetaConfig().updated(pipeline=dict(depth=0))
    with pytest.raises(ValueError, match="num_workers"):
        HetaConfig().updated(pipeline=dict(num_workers=-1))
    # derived CLI flags
    ap = argparse.ArgumentParser()
    add_config_args(ap)
    args = ap.parse_args(["--prefetch-depth", "4",
                          "--snapshot-policy", "stale", "--num-workers", "2"])
    got = config_from_args(args)
    assert got.pipeline.depth == 4
    assert got.pipeline.snapshot == "stale"
    assert got.pipeline.num_workers == 2
    assert config_from_args(ap.parse_args([])).pipeline == PipelineConfig()


@pytest.mark.parametrize("field,flat,flag", [
    ("enabled", "pipeline", "--pipeline"),
    ("arena", "batch_arena", "--batch-arena"),
    ("pin_workers", "pin_workers", "--pin-workers"),
])
def test_removed_pipeline_fields_are_refused(field, flat, flag):
    """``pipeline.enabled``, ``arena`` and ``pin_workers`` are gone: a
    dict, flat kwargs, an update or a CLI flag that names one is refused,
    not silently dropped."""
    d = HetaConfig().to_dict()
    d["pipeline"][field] = True
    with pytest.raises(TypeError, match=field):
        HetaConfig.from_dict(d)
    with pytest.raises(TypeError, match=flat):
        HetaConfig.from_flat_kwargs(**{flat: True})
    assert flat not in HetaConfig().to_flat_kwargs()
    with pytest.raises(TypeError, match=field):
        HetaConfig().updated(pipeline={field: True})
    ap = argparse.ArgumentParser()
    add_config_args(ap)
    for arg in (flag, "--no-" + flag[2:]):
        with pytest.raises(SystemExit):
            ap.parse_args([arg])


def test_checkpoint_and_fault_config_round_trips():
    cfg = HetaConfig().updated(
        checkpoint=dict(every_steps=5, dir="/tmp/ck", keep=3),
        faults=dict(max_worker_restarts=4, worker_backoff_s=0.1,
                    arena_write_timeout_s=12.0),
        serve=dict(deadline_ms=250.0, flush_retries=1, retry_backoff_ms=0.5,
                   breaker_threshold=2, breaker_cooldown_ms=100.0),
    )
    assert HetaConfig.from_dict(cfg.to_dict()) == cfg
    flat = cfg.to_flat_kwargs()
    assert flat["checkpoint_every_steps"] == 5
    assert flat["max_worker_restarts"] == 4
    assert flat["serve_breaker_threshold"] == 2
    assert HetaConfig.from_flat_kwargs(**flat) == cfg

    with pytest.raises(ValueError, match="every_steps"):
        HetaConfig().updated(checkpoint=dict(every_steps=-1))
    with pytest.raises(ValueError, match="checkpoint.dir"):
        HetaConfig().updated(checkpoint=dict(every_steps=2))
    with pytest.raises(ValueError, match="max_worker_restarts"):
        HetaConfig().updated(faults=dict(max_worker_restarts=-1))
    with pytest.raises(ValueError, match="breaker_threshold"):
        HetaConfig().updated(serve=dict(breaker_threshold=0))
    with pytest.raises(ValueError, match="deadline_ms"):
        HetaConfig().updated(serve=dict(deadline_ms=-1.0))

    ap = argparse.ArgumentParser()
    add_config_args(ap)
    args = ap.parse_args([
        "--checkpoint-every-steps", "2", "--checkpoint-dir", "/tmp/ck",
        "--checkpoint-keep", "1", "--max-worker-restarts", "3",
        "--worker-backoff-s", "0.2", "--serve-deadline-ms", "100",
        "--serve-flush-retries", "1", "--serve-breaker-threshold", "5",
    ])
    got = config_from_args(args)
    assert got.checkpoint.every_steps == 2 and got.checkpoint.dir == "/tmp/ck"
    assert got.checkpoint.keep == 1
    assert got.faults.max_worker_restarts == 3
    assert got.faults.worker_backoff_s == 0.2
    assert got.serve.deadline_ms == 100.0
    assert got.serve.flush_retries == 1
    assert got.serve.breaker_threshold == 5
