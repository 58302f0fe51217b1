"""That ``correct`` can come out false: the control (the reference in the
program's place, in float8 arithmetic) and each fault a training cell can
have, at the rehearsal size on the CPU.

The look for a chip is skipped (``rehearse``); the rest of a run is the
harness's own: the runner builds the engine, drives its first steps and the
window through the same call, and compares with the reference under the
limits of the cell's file.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402

TRAIN_CELLS = [w["name"] for w in bench.load_json(ROOT, "BENCHMARK.json")[
    "workloads"] if bench.load_json(bench.HERE, "workloads", w["name"]
                                    + ".json")["runner"] == "train"]


def over(run):
    return {n for n, (v, lim, _) in run["compared"].items() if not v <= lim}


@pytest.fixture(scope="module", params=TRAIN_CELLS)
def train(request):
    return bench.make_context(request.param, 2147483659, seconds=0.5,
                              rehearse=True)


def test_sound_run_is_correct(train):
    ctx, runner = train
    run = runner.run(dict(ctx))
    assert run["correct"], run["compared"]


def test_control_in_float8_is_not_correct(train):
    ctx, runner = train
    lines = list(runner.readings(dict(ctx), [2147483659], "float8",
                                 {2147483659}, set()))
    control = next(l for l in lines if l["kind"] == "control_float8")
    limits = ctx["cell"]["limits"]
    assert [n for n, v in control["numbers"].items()
            if n in limits and v > limits[n]], control["numbers"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        train, monkeypatch):
    ctx, runner = train
    import jax
    import jax.numpy as jnp

    def frozen(self, tokens, labels):
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
        _, _, loss = self.eng.step(copy(self.params), copy(self.opt),
                                   tokens, labels)
        return loss

    monkeypatch.setattr(runner.Trainer, "step", frozen)
    run = runner.run(dict(ctx))
    assert not run["correct"] and "change_norm" in over(run), run["compared"]


def test_half_of_the_batch_left_out_is_not_correct(train, monkeypatch):
    ctx, runner = train
    sound = runner.Trainer.step

    def half(self, tokens, labels):
        n = tokens.shape[0] // 2
        return sound(self, tokens[:n], labels[:n])

    monkeypatch.setattr(runner.Trainer, "step", half)
    run = runner.run(dict(ctx))
    assert not run["correct"] and over(run), run["compared"]


# ---------------------------------------------------------------- serving

SERVE_CELLS = [w["name"] for w in bench.load_json(ROOT, "BENCHMARK.json")[
    "workloads"] if bench.load_json(bench.HERE, "workloads", w["name"]
                                    + ".json")["runner"] == "serve"]


@pytest.fixture(scope="module", params=SERVE_CELLS)
def serve(request):
    return bench.make_context(request.param, 2147483693, seconds=1.5,
                              rehearse=True)


def test_sound_serving_run_is_correct(serve):
    ctx, runner = serve
    run = runner.run(dict(ctx, t_start=0.0))
    assert run["correct"], (run["compared"], run["notes"])
    assert run["counts"]["prefix_hit_tokens"] > 0


def test_serving_control_in_float8_is_not_correct(serve):
    ctx, runner = serve
    lines = list(runner.readings(dict(ctx), [2147483693], "float8",
                                 {2147483693}, set()))
    control = next(l for l in lines if l["kind"] == "control_float8")
    limits = ctx["cell"]["limits"]
    assert [n for n in limits if control["numbers"][n] > limits[n]], lines


def test_a_token_altered_where_it_is_produced_is_not_correct(
        serve, monkeypatch):
    ctx, runner = serve
    from paddle_tpu.serving.engine import Engine

    sound = Engine._sample_token

    def altered(self, logits_row, req):
        token = sound(self, logits_row, req)
        if len(req.tokens) % 5 == 0:
            token = (token + 1) % self.cfg.vocab_size
        return token

    monkeypatch.setattr(Engine, "_sample_token", altered)
    run = runner.run(dict(ctx, t_start=0.0))
    assert not run["correct"] and over(run), run["compared"]
