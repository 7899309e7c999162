"""Device scopes (``mxnet_tpu/trace/scopes.py``): every operation of the
fused step carries the name of the graph node or step part that made it,
the program's own table says which, it is built on request only, and it
never reads names older than the code that traced the step."""
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.compile_cache import cached_jit
from mxnet_tpu.compile_cache.jaxcache import count_backend_compiles
from mxnet_tpu.ops import transformer as tf_ops
from mxnet_tpu.trace import scopes

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))
from jax_cache import jax_cache_dir  # noqa: E402,F401

INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s.*?\bop_name="([^"]*)"', re.M)


def _small_module(batch=8, width=16, classes=12):
    """Two plain nodes (``fc1``, ``fc2``) around an activation, and one op
    that declares its own scope (``SoftmaxCELoss``: ``lm_loss``)."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=width,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="tanh", name="act1")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    net = mx.sym.MakeLoss(mx.sym.SoftmaxCELoss(
        net, mx.sym.Variable("softmax_label"), name="loss"), name="lm")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, width))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params={
        "learning_rate": 0.1, "momentum": 0.9})
    rng = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(batch, width).astype("float32"))],
        label=[mx.nd.array(rng.randint(0, classes, (batch,))
                           .astype("float32"))], pad=0)
    return mod, batch


def _step(mod, batch):
    mod.forward_backward(batch)
    mod.update()


def test_the_steps_table_names_forward_backward_and_update():
    mod, batch = _small_module()
    _step(mod, batch)
    assert mod._fused is not None
    table = mx.trace.program_scopes("fused:step")
    text = mod._fused._step.optimized_hlo()
    assert text.startswith("HloModule jit_" + scopes.module_name("step"))
    by_pass = {"forward": set(), "backward": set(), "update": set()}
    for instruction, op_name in INSTRUCTION.findall(text):
        scope = table.get(instruction)
        if scope is None:
            continue
        assert scope == scopes.resolve(op_name)
        if "transpose(jvp(" in op_name:
            by_pass["backward"].add(scope)
        elif scope.startswith("optimizer."):
            by_pass["update"].add(scope)
        else:
            by_pass["forward"].add(scope)
    assert {"fullyconnected.fc1", "fullyconnected.fc2",
            "lm_loss"} <= by_pass["forward"]
    assert {"fullyconnected.fc1", "fullyconnected.fc2"} \
        <= by_pass["backward"]
    assert by_pass["update"] == {"optimizer.fc1_weight", "optimizer.fc1_bias",
                                 "optimizer.fc2_weight", "optimizer.fc2_bias"}
    # the op's own scope, not its node's generic one
    assert "softmaxceloss.loss" not in table.values()
    assert {scopes.kind_of(s) for s in table.values()} >= {
        "fullyconnected", "activation", "lm_loss", "optimizer"}
    # the table is kept, and the time it took is a span
    assert mx.trace.program_scopes("fused:step") is table
    spans = mx.trace.span_events(names=[scopes.TABLE_SPAN])
    assert spans and spans[-1]["args"] == {"program": "fused:step",
                                           "instructions": len(table)}


def _enter(outer, inner_layer):
    """The op_names of what is computed under ``node_scope(*outer)`` and,
    inside it, ``layer_scope("attn", inner_layer)`` (None: none)."""
    def f(x):
        with tf_ops.node_scope(*outer):
            if inner_layer is None:
                return jnp.sin(x)
            with tf_ops.layer_scope("attn", inner_layer):
                return jnp.sin(x)
    text = jax.jit(jax.grad(lambda x: f(x).sum())).lower(
        jnp.ones((4,))).as_text(debug_info=True)
    # every operation but the gradient's seed, which no scope made
    return [n for n in re.findall(r'loc\("(jit[^"]*)"', text)
            if "jvp()" not in n]


@pytest.mark.parametrize("outer,inner_layer,scope", [
    # a plain node: the generic scope of its op type and name
    ((None, "Convolution", "stage1_conv1"), None,
     "convolution.stage1_conv1"),
    # a node that carries __scope__ keeps it, unchanged
    (("mla_q.l3", "FullyConnected", "l3_q_proj"), None, "mla_q.l3"),
    # declared over generic: the op names itself inside its node's scope
    ((None, "CausalSelfAttention", "l0_attn"), 0, "attn.l0"),
    # the outermost of nested declared scopes
    (("x.l0", "CausalSelfAttention", "l0_attn"), 0, "x.l0"),
    # a prefix goes before the op's own scope and before the generic one
    (("mtp.", "CausalSelfAttention", "mtp_attn"), -1, "mtp.attn"),
    (("mtp.", "FullyConnected", "mtp_head"), None,
     "mtp.fullyconnected.mtp_head"),
    # the step parts' and the old one-argument form
    (("optimizer.fc1_weight",), None, "optimizer.fc1_weight"),
])
def test_precedence_declared_over_generic_and_outermost_declared(
        outer, inner_layer, scope):
    names = _enter(outer, inner_layer)
    assert names, "no operation was lowered"
    for op_name in names:                 # forward and backward
        assert scopes.resolve(op_name) == scope, op_name
    assert any("transpose(jvp(" in n for n in names)
    assert scopes.kind_of(scope) == scope.partition(".")[0]
    assert getattr(tf_ops._scope, "prefix", "") == ""


def test_sort_of_says_which_sort_a_scope_was_entered_as(monkeypatch):
    for name in ("_declared", "_generic", "_enclosing"):
        monkeypatch.setattr(scopes, name, set())
    monkeypatch.setattr(scopes, "_adopted", {})
    with scopes.declared("mlp.l0"), scopes.generic("rmsnorm.n"), \
            scopes.enclosing("loop"):
        pass
    scopes.adopt("ragged-dot", "moe_experts")
    assert [scopes.sort_of(s) for s in (
        "mlp.l0", "rmsnorm.n", "loop", "moe_experts")] == [
            "declared", "generic", "enclosing", "adopted"]
    # a kind is no scope, and neither is a name nothing entered
    assert scopes.sort_of("mlp") is None
    assert scopes.sort_of("jit(step_s2)") is None
    # entered as two sorts: the one ``resolve`` would take it for
    with scopes.generic("mlp.l0"), scopes.declared("moe_experts"):
        pass
    assert scopes.sort_of("mlp.l0") == "declared"
    assert scopes.sort_of("moe_experts") == "declared"
    assert "sort_of" in scopes.__all__


def test_what_is_no_scope_resolves_to_nothing():
    with scopes.declared("attn.l7"), scopes.generic("rmsnorm.n"):
        pass
    assert scopes.resolve("jit(step_s2)/jit(main)/add") is None
    assert scopes.resolve("jit(step_s2)/jvp()/reduce_sum") is None
    assert scopes.resolve("jit(attn.l7)/mul") is None      # a function's name
    assert scopes.resolve("jit(step_s2)/while/body/closed_call/mul") is None
    assert scopes.resolve(
        "jit(step_s2)/transpose(jvp(rmsnorm.n))/while/body/attn.l7/mul") \
        == "attn.l7"
    text = ('  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
            'metadata={op_name="jit(s)/jvp(rmsnorm.n)/mul" source_line=3}\n'
            '  ROOT copy.1 = f32[8]{0} copy(%fusion.7)\n'
            '  %add.2 = f32[8]{0} add(%a, %b), metadata={op_name="jit(s)/add"}\n')
    assert scopes.table_of(text) == {"fusion.7": "rmsnorm.n"}
    # an instruction that spans lines (a Pallas kernel's custom call),
    # and a kernel the compiler wrote under a name of its own
    text += ('  %splash_fwd.1 = (f32[8]{0}) custom-call(%fusion.7), '
             'frontend_attributes={kernel_metadata={\n"xprof": "{\\"q\\": 1}"\n'
             '}}, metadata={op_name="jit(s)/jvp(x)/attn.l7/pallas_call"}, '
             'backend_config={"body":"TUz="}\n'
             '  %ragged-dot-none.4 = bf16[8,8]{1,0} custom-call(%a, %b), '
             'metadata={op_name="ragged-dot-none"}\n')
    import mxnet_tpu.moe.dispatch  # noqa: F401  (adopts "ragged-dot")
    assert scopes.table_of(text) == {
        "fusion.7": "rmsnorm.n", "splash_fwd.1": "attn.l7",
        "ragged-dot-none.4": "moe_experts"}
    assert scopes.resolve("ragged-dot-metadata") == "moe_experts"
    assert scopes.resolve("jit(s)/ragged_dot") is None


def test_an_untraced_run_builds_no_table(monkeypatch):
    """Bind, the first step and the steps after it lower nothing for the
    table: its lowering is the request's, and only the first one's."""
    lowerings = []
    mod, batch = _small_module(batch=4, width=8, classes=6)
    with count_backend_compiles() as counter:
        _step(mod, batch)
        step = mod._fused._step
        monkeypatch.setattr(step, "_jit", _CountingJit(step._jit, lowerings))
        at_first_step = counter.count
        _step(mod, batch)
        _step(mod, batch)
        assert lowerings == [] and not step._entries
        assert counter.count == at_first_step
        assert scopes._programs["fused:step"] == [step, None]
        table = mx.trace.program_scopes("fused:step")
        assert len(lowerings) == 1 and table
        assert mx.trace.program_scopes("fused:step") is table
        assert len(lowerings) == 1
        # where jit's own cache holds the executable, the request
        # compiles nothing either
        assert counter.count - at_first_step <= 1


class _CountingJit:
    """A jitted function that counts its ``lower`` calls."""

    def __init__(self, jitted, lowerings):
        self._jitted, self._lowerings = jitted, lowerings

    def __call__(self, *args):
        return self._jitted(*args)

    def lower(self, *args):
        self._lowerings.append(args)
        return self._jitted.lower(*args)


def test_the_table_keeps_no_donated_buffer_alive():
    mod, batch = _small_module(batch=4, width=8, classes=6)
    _step(mod, batch)
    leaves = jax.tree_util.tree_leaves(mod._fused._step._specs)
    assert leaves and not any(isinstance(x, jax.Array) for x in leaves)
    assert all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves
               if hasattr(x, "shape"))


def _make_step(scoped, name):
    def step(w, x):
        if scoped:
            with scopes.declared("attn.l0"):
                h = jnp.tanh(x @ w)
        else:
            h = jnp.tanh(x @ w)
        return h.sum()
    step.__name__ = name
    return step


def test_a_cache_filled_without_scopes_does_not_serve_its_names(
        jax_cache_dir):
    """The key of JAX's persistent cache strips the scopes, so the same
    operations under the same module name are served the executable
    compiled before the scopes existed; the scheme in the module's name
    is what keeps the step's table true."""
    w, x = jnp.ones((32, 32)), jnp.ones((4, 32))
    with count_backend_compiles() as counter:
        cached_jit(_make_step(False, "step"), name="t:plain")(w, x)
        assert (counter.count, counter.cache_hits) == (1, 0)
        # the trap: scopes alone do not change the key
        stale = cached_jit(_make_step(True, "step"), name="t:stale")
        stale(w, x)
        assert counter.cache_hits == 1
        assert scopes.table_of(stale.optimized_hlo()) == {}
        # the scheme in the name does
        named = cached_jit(_make_step(True, scopes.module_name("step")),
                           name="t:named")
        named(w, x)
        assert counter.cache_hits == 1
        table = scopes.table_of(named.optimized_hlo())
        assert table and set(table.values()) == {"attn.l0"}
        # and a warm start of the named program reads the same scopes
        warm = cached_jit(_make_step(True, scopes.module_name("step")),
                          name="t:warm")
        warm(w, x)
        assert counter.cache_hits == 2
        assert scopes.table_of(warm.optimized_hlo()) == table


def test_a_warmed_programs_table_reads_the_entry_that_runs():
    """A program that ``warm()`` compiled was traced by this process: the
    table's request lowers nothing and compiles nothing, it reads the
    text of the entry the next call dispatches to."""
    w, x = jnp.ones((16, 16)), jnp.ones((4, 16))
    warmed = cached_jit(_make_step(True, "warmed_step"), name="t:warmed")
    assert warmed.warm(w, x) == "compiled"
    assert warmed.warm(w, x) == "present"
    lowerings = []
    warmed._jit = _CountingJit(warmed._jit, lowerings)
    with count_backend_compiles() as counter:
        table = scopes.table_of(warmed.optimized_hlo())
        warmed(w, x)
        assert scopes.table_of(warmed.optimized_hlo()) == table
    assert lowerings == [] and counter.count == 0
    assert table and set(table.values()) == {"attn.l0"}
