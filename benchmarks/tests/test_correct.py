"""How ``correct`` is decided, shown to fail: the control (the plain
reference in float8, in the program's place) comes out as not correct,
and so does a run whose timed path is broken underneath.

The limits here are the toy configurations' own (float32 on the CPU);
the cells' limits were read on the chip at the cells' sizes (PERF.md).
"""
import numpy as np
from conftest import toy_spec

from benchmarks import run
from benchmarks.drivers import serve_open_loop, train_fit


def context(cell, seed=5, seconds=2.0):
    return run.Context(toy_spec(cell), seed, seconds)


def test_training_control_in_float8_is_not_correct():
    ctx = context("resnet50.fit-b256")
    got = train_fit.readings(ctx)
    limits = ctx.config["correct"]
    names = ("loss_gap", "grad_trace_gap", "param_change_gap")
    assert all(got["program"][n] <= limits[n]["limit"] for n in names)
    assert any(got["control_fp8"][n] > limits[n]["limit"] for n in names)
    # a part of the batch left out is what the loss is there to catch
    assert got["fault_partial_batch"]["loss_gap"] > \
        limits["loss_gap"]["limit"]


def test_serving_control_in_float8_is_not_correct():
    ctx = context("mistral7b.chat-steady")
    got = serve_open_loop.readings(ctx)
    limit = ctx.config["correct"]["served_logit_gap"]["limit"]
    assert got["program"]["served_logit_gap"] <= limit
    assert got["control_fp8"]["served_logit_gap"] > limit


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        toy_cell, monkeypatch):
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    make = ComputationGraph._make_train_loop

    def broken(self):
        loop = make(self)

        def same_state(params, opt_state, state, *rest):
            import jax
            keep = jax.tree.map(lambda a: a + 0, (params, opt_state, state))
            *_, losses = loop(params, opt_state, state, *rest)
            return (*keep, losses)
        return same_state

    monkeypatch.setattr(ComputationGraph, "_make_train_loop", broken)
    result = toy_cell("resnet50.fit-b256", seconds=1.0)
    assert result["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(
        toy_cell, monkeypatch):
    from deeplearning4j_tpu.serving.gateway import TokenStream
    push = TokenStream.push

    def altered(self, tok):
        n = len(self._tokens)
        push(self, (tok + 1) % 512 if n == 3 else tok)

    monkeypatch.setattr(TokenStream, "push", altered)
    result = toy_cell("mistral7b.chat-steady", seconds=2.0)
    assert result["correct"] is False
