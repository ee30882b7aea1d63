"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole run of a tiny cell on the CPU (the harness's look
for a chip skipped) with one fault planted in the program, under the real
cells' limits.  The fault a one-chip cell cannot have is left out: with the
4 partitions folded onto one chip the RAF exchange (``psum``) runs over an
axis of size 1, so leaving it out changes nothing.
"""

import pytest

import tinycell


@pytest.fixture(params=["rgcn", "hgt"])
def model(request):
    return request.param


def test_sound_run_is_correct(tmp_path, model):
    r = tinycell.run(tmp_path, model)
    assert r["correct"] is True, r["checks"]


def test_step_that_returns_its_state_unchanged(tmp_path, monkeypatch, model):
    import jax
    import jax.numpy as jnp

    from repro.api.executors import RafSpmdExecutor

    real = RafSpmdExecutor.step_staged

    def unchanged(self, sess, plan, state, batch, arrays):
        keep = jax.tree.map(lambda a: jnp.array(a, copy=True), state)
        _, loss, dt = real(self, sess, plan, state, batch, arrays)
        return keep, loss, dt

    monkeypatch.setattr(RafSpmdExecutor, "step_staged", unchanged)
    r = tinycell.run(tmp_path, model)
    assert r["correct"] is False
    assert r["checks"]["grad_gap"]["value"] > r["checks"]["grad_gap"]["limit"]


def test_half_the_batch_left_out(tmp_path, monkeypatch, model):
    import jax.numpy as jnp

    from repro.core import raf_spmd

    class HalfMean:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def mean(x, *args, **kw):
            return jnp.mean(x[: x.shape[0] // 2], *args, **kw)

    monkeypatch.setattr(raf_spmd, "jnp", HalfMean())
    r = tinycell.run(tmp_path, model)
    assert r["correct"] is False


def test_labels_altered_where_they_are_staged(tmp_path, monkeypatch, model):
    from repro.core import raf_spmd

    real = raf_spmd.stack_batch_host

    def shifted(recipe, batch, tables, *args, **kw):
        out = real(recipe, batch, tables, *args, **kw)
        out["labels"] = (out["labels"] + 1) % 349
        return out

    monkeypatch.setattr(raf_spmd, "stack_batch_host", shifted)
    r = tinycell.run(tmp_path, model)
    assert r["correct"] is False
    assert r["checks"]["loss_gap"]["value"] > r["checks"]["loss_gap"]["limit"]


def test_a_sampled_neighbor_altered(tmp_path, monkeypatch, model):
    from repro.graph import sampler

    real = sampler.sample_neighbors

    def to_the_next_parent(csr, parents, parent_mask, fanout, rng):
        import numpy as np

        idx, mask = real(csr, parents, parent_mask, fanout, rng)
        return np.roll(idx, 1, axis=0), mask

    monkeypatch.setattr(sampler, "sample_neighbors", to_the_next_parent)
    r = tinycell.run(tmp_path, model)
    assert r["correct"] is False
    assert r["checks"]["batch_faults"]["value"] > 0
