"""The counter heads of a training symbol (``mxnet_tpu/trace/heads.py``):
the table that ``benchmark/layer_metrics`` reads by span and counter
name, a head that only a test registers, and the order two heads of one
symbol are read in."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu.models import smallthinker_lm              # noqa: E402
from mxnet_tpu.trace import heads                         # noqa: E402

# what the per-layer metrics' readers look for: a renamed span or counter
# nulls a metric and fails nothing else
TABLE = [("moe_load", "fit:moe_load", "moe:load", True),
         ("mtp_loss", "fit:mtp_loss", "mtp:loss", False),
         ("diffusion_noise", "fit:diffusion_noise", "diffusion:noise", False),
         ("moe_act_zeros", "fit:moe_act_zeros", "moe:act_zeros", False),
         ("loop_exit", "fit:loop_exit", "loop:exit", False),
         ("dsa_select", "fit:dsa_select", "dsa:select", False)]
BATCH = 4


@pytest.mark.parametrize("at", range(len(TABLE)))
def test_the_table_holds_each_head_where_its_readers_look(at):
    name, span, counter, always = TABLE[at]
    head = heads.HEADS[at]
    assert (head.name, head.span, head.counter, head.always) \
        == (name, span, counter, always)
    assert callable(head.find) and callable(head.emit)


def test_the_table_holds_six_heads_and_a_plain_symbol_none():
    assert [h.name for h in heads.HEADS] == [row[0] for row in TABLE]
    assert heads.find_all(_mlp()) == []
    # a variable of a head's name, or another op of it, is no head
    x = mx.sym.Variable("loop_exit")
    other = mx.sym.Group([x, mx.sym.sum(x, name="diffusion_noise")])
    assert heads.LOOP_EXIT.find(other) is None
    assert heads.DIFFUSION_NOISE.find(other) is None


def _mlp(probe=None):
    x = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(x, num_hidden=3,
                                                     name="fc"),
                               name="softmax")
    if probe is None:
        return net
    pair = mx.sym.Concat(mx.sym.Reshape(mx.sym.sum(x), shape=(1,)),
                         mx.sym.Reshape(mx.sym.sum(x * 0.0 + 1.0),
                                        shape=(1,)), dim=0)
    return mx.sym.Group([net, mx.sym.BlockGrad(pair, name=probe)])


def _fit(net, X, Y, names, batch=BATCH):
    mod = mx.mod.Module(net, context=mx.cpu(0))
    since = time.perf_counter_ns()
    mod.fit(mx.io.NDArrayIter(X, Y, batch_size=batch), num_epoch=1,
            eval_metric=mx.metric.OutputMean(0), optimizer="sgd",
            initializer=mx.init.Normal(0.02),
            optimizer_params={"learning_rate": 0.1})
    counters = mx.trace.counter_events(
        [h.counter for h in names], since_ns=since)
    spans = mx.trace.span_events(
        names=["fit:step", "fit:update_metric"] + [h.span for h in names],
        since_ns=since)
    return mod, counters, spans


@pytest.mark.parametrize("traced", [True, False])
def test_a_seventh_head_needs_an_entry_and_no_edit_of_the_runner(
        monkeypatch, traced):
    probe = heads.counter_head("input_probe", "fit:input_probe",
                               "input:probe", columns=("total", "cells"))
    monkeypatch.setattr(heads, "HEADS", heads.HEADS + [probe])
    rng = np.random.RandomState(0)
    X = rng.rand(3 * BATCH, 5).astype(np.float32)
    Y = rng.randint(0, 3, (3 * BATCH,)).astype(np.float32)
    was = mx.trace.enabled()
    mx.trace.reset()
    mx.trace.set_enabled(traced)
    try:
        mod, counters, spans = _fit(_mlp("input_probe"), X, Y, [probe])
    finally:
        mx.trace.reset()         # the ring is the process's: leave none
        mx.trace.set_enabled(was)
    assert mod._fused.head("input_probe") == 1
    assert mod._fused.head("moe_load") is None
    assert mod._fused.head("no_such_head") is None
    if not traced:
        assert not counters and not spans
        return
    seen = [e for e in counters if e["name"] == "input:probe"]
    assert [e["cat"] for e in seen] == ["train"] * 3
    assert [e["args"] for e in seen] == [
        {"total": pytest.approx(float(X[i:i + BATCH].sum()), rel=1e-5),
         "cells": float(BATCH * 5)} for i in range(0, len(X), BATCH)]
    reads = [e for e in spans if e["name"] == "fit:input_probe"]
    steps = [(e["ts"], e["ts"] + e["dur"]) for e in spans
             if e["name"] == "fit:step"]
    scored = [(e["ts"], e["ts"] + e["dur"]) for e in spans
              if e["name"] == "fit:update_metric"]
    assert len(reads) == len(steps) == 3
    for e in reads:
        assert e["cat"] == "train"
        assert any(a <= e["ts"] and e["ts"] + e["dur"] <= b
                   for a, b in steps)
        assert not any(a <= e["ts"] and e["ts"] + e["dur"] <= b
                       for a, b in scored)


def test_a_symbol_without_heads_costs_no_read_of_the_outputs(monkeypatch):
    rng = np.random.RandomState(1)
    X = rng.rand(2 * BATCH, 5).astype(np.float32)
    Y = rng.randint(0, 3, (2 * BATCH,)).astype(np.float32)
    mod, _, _ = _fit(_mlp(), X, Y, [])
    assert mod._fused is not None and mod._fused.heads == []

    def refuse(*a, **k):
        raise AssertionError("outputs read for no head")

    monkeypatch.setattr(mod, "get_outputs", refuse)
    mod._note_train_outputs()


def test_two_heads_of_one_symbol_are_read_in_the_tables_order():
    net = smallthinker_lm(
        num_layers=2, hidden_size=32, layer_types=["full", "sliding"],
        num_heads=6, num_kv_heads=2, head_dim=8, window=6, rope_theta=1.5e6,
        num_experts=16, experts_per_tok=3, expert_width=24, vocab_size=50,
        seq_len=16, experts_held=4, first_expert=4, rms_eps=1e-6,
        act_zeros=True)
    rng = np.random.RandomState(2)
    X = rng.randint(0, 50, (3 * 2, 16)).astype(np.int32)
    names = [heads.MOE_LOAD, heads.MOE_ACT_ZEROS]
    was = mx.trace.enabled()
    mx.trace.reset()
    mx.trace.set_enabled(True)
    try:
        mod, counters, spans = _fit(net, X, np.roll(X, -1, 1), names,
                                    batch=2)
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
    assert [h.name for h, _ in mod._fused.heads] \
        == ["moe_load", "moe_act_zeros"]
    assert mod._fused.head("moe_load") \
        == (1, ["l0_moe_dispatch", "l1_moe_dispatch"])
    assert mod._fused.head("moe_act_zeros") \
        == (2, ["l0_moe_share", "l1_moe_share"])
    reads = sorted((e["ts"], e["name"]) for e in spans
                   if e["name"] in ("fit:moe_load", "fit:moe_act_zeros"))
    assert [n for _, n in reads] == ["fit:moe_load", "fit:moe_act_zeros"] * 3
    # the ring holds a thread's samples oldest first: a step's load
    # samples, a block each, then its zeros
    assert [(e["name"], e["id"]) for e in counters] == [
        ("moe:load", "l0_moe_dispatch"), ("moe:load", "l1_moe_dispatch"),
        ("moe:act_zeros", "l0_moe_share"),
        ("moe:act_zeros", "l1_moe_share")] * 3
    assert {e["cat"] for e in counters} == {"moe"}
