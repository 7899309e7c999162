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


def placed_on_rows(net):
    """What stands between q's and k's projections and attention since
    ISSUE 70: every ``HeadNormRotary`` node as ``(name, scope, the
    keywords it was made with, its inputs' names)``, in the graph's
    order, a loop node's body included."""
    import mxnet_tpu as mx
    found = []
    for node in mx.symbol._topo(net._heads):
        if node.is_variable:
            continue
        if node.op.name == "Repeat":
            found += placed_on_rows(node.params["body"])
        if node.op.name == "HeadNormRotary":
            found.append((node.name, node.attrs.get("__scope__"),
                          dict(node.params),
                          [i[0].name for i in node.inputs]))
    return found
