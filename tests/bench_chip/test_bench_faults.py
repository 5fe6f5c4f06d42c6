"""Whole runs with the timed path broken underneath: ``correct`` must come
out false for each fault that these cells can have."""

import jax.numpy as jnp
import pytest


def _alter_row(outs: dict) -> dict:
    """One answer altered where it is produced: row 0 of every output + 1."""
    return {k: v.at[0].add(1.0) for k, v in outs.items()}


def _alter(monkeypatch, fault: str) -> None:
    from repro.backend.aot import AotModel
    from repro.serve import BatchedModel

    batch_async, aot_run = BatchedModel.run_batch_async, AotModel.run
    if fault == "answer_altered":
        monkeypatch.setattr(BatchedModel, "run_batch_async", lambda self, p, xs: _alter_row(batch_async(self, p, xs)))
        monkeypatch.setattr(AotModel, "run", lambda self, p, x: _alter_row({k: jnp.asarray(v) for k, v in aot_run(self, p, x).items()}))
    elif fault == "half_batch":
        # half of each batch left out: its slots get the other half's answers
        def half(self, p, xs):
            h = xs[: max(1, len(xs) // 2)]
            return batch_async(self, p, (h * 2)[: len(xs)])

        monkeypatch.setattr(BatchedModel, "run_batch_async", half)


@pytest.mark.parametrize(
    "cell, fault",
    [
        ("resnet8_cifar10.offline_b256", "answer_altered"),
        ("resnet8_cifar10.offline_b256", "half_batch"),
        ("resnet8_cifar10.single_stream", "answer_altered"),
        ("dscnn_kws.single_stream", "answer_altered"),
        ("mobilenetv1_025_vww.server_poisson", "answer_altered"),
        ("mobilenetv1_025_vww.server_poisson", "half_batch"),
    ],
)
def test_broken_path_is_not_correct(small_root, run_cell, monkeypatch, cell, fault):
    _alter(monkeypatch, fault)
    line = run_cell(small_root, cell)
    assert line["correct"] is False
    assert line["checks"]["max_abs_err"]["value"] > line["checks"]["max_abs_err"]["limit"]
