"""What a training symbol shows the world: the names and shapes of its
arguments, outputs and auxiliary states, in order, as one sha256.  A
checkpoint, a cell's ``reference.weights`` and the reference files map
by these, so a PR that means to move a builder's nodes and nothing
else pins the value its parent gave."""
import hashlib
import json


def signature(net, **inputs):
    args, outs, aux = net.infer_shape(**inputs)
    listed = [list(zip(net.list_arguments(), args)),
              list(zip(net.list_outputs(), outs)),
              list(zip(net.list_auxiliary_states(), aux))]
    return hashlib.sha256(json.dumps(listed).encode()).hexdigest()


def nodes(net, op_name):
    """The symbol's nodes of one op, in the graph's order."""
    import mxnet_tpu as mx
    return [n for n in mx.symbol._topo(net._heads)
            if not n.is_variable and n.op.name == op_name]
