"""Checkpoints of the tiny language models as the commit before PR 36
wrote them (``tests/data/before_pr36``: ``save_checkpoint`` of the
model's own test builder at seed 36, and beside it the loss that commit
read and its parameters' shapes).  PR 36 gave the dispatch node an
eighth output, ``order``, and the combine node a fourth input for it,
and rewrote the sorted layout's backward passes: none of it may show in
a parameter, in the arguments of a saved graph or in a loss."""
import json
import os

import numpy as np

import mxnet_tpu as mx

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data", "before_pr36")


def _module(net, tokens, labels, args, auxs):
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", tokens.shape)],
             label_shapes=[("softmax_label", labels.shape)])
    mod.init_params(mx.init.Zero(), arg_params=args, aux_params=auxs)
    return mod, mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)], pad=0)


def check_checkpoint_written_before_pr36(name, net, tokens, labels,
                                         optimizer_params):
    """The old file loads into its own saved graph and into today's
    ``net`` with the arguments it always had, both read the loss the old
    commit read, and a trained step of today's leaves the names and
    shapes that commit had."""
    prefix = os.path.join(DATA, name + "_before_pr36")
    with open(prefix + "-expected.json") as f:
        want = json.load(f)
    saved, args, auxs = mx.model.load_checkpoint(prefix, 1)
    assert saved.list_arguments() == net.list_arguments()
    assert saved.list_auxiliary_states() == net.list_auxiliary_states()
    assert saved.list_outputs() == net.list_outputs()
    for graph in (saved, net):
        mod, batch = _module(graph, tokens, labels, args, auxs)
        mod.forward(batch, is_train=False)
        outs = [o.asnumpy().astype(np.float64) for o in mod.get_outputs()]
        assert np.isclose(outs[0].mean(), want["loss"], rtol=1e-6, atol=0)
        assert np.allclose([o.sum() for o in outs], want["outputs"],
                           rtol=1e-5, atol=1e-6)
    mod.init_optimizer(optimizer="adam", optimizer_params=optimizer_params)
    assert mod._fused is not None
    mod.forward_backward(batch)
    mod.update()
    trained, aux = mod.get_params()
    assert {k: list(v.shape) for k, v in trained.items()} == want["shapes"]
    assert {k: list(v.shape) for k, v in aux.items()} == want["aux_shapes"]
    assert any(np.abs(trained[k].asnumpy() - args[k].asnumpy()).max() > 0
               for k in trained)
