"""Counter heads: outputs of a training symbol that feed trace counters.

A builder that wants a number a step out of its objective groups one
more output onto its symbol, behind a ``BlockGrad`` so that it carries
no gradient.  The output travels with the step's other outputs, and the
fit loop reads it on the host after the metric update has already
waited for the step: no device sync of its own.  This file is the whole
contract between such a builder and the runner: a :class:`Head` says
how its output is found in a symbol, the span it is read under and what
it emits from one step's outputs; :data:`HEADS` lists the ones that
exist, in the order they are read.  ``FusedTrainStep`` finds each once
in its symbol (``step.heads``, ``step.head(name)``) and
``Module._note_train_outputs`` is the one loop that reads them, so a
new head is one entry here and no edit of the runner
(``docs/observability.md``).
"""
from __future__ import annotations

import collections

from .. import trace as _trace


Head = collections.namedtuple(
    "Head", "name span counter find emit always", defaults=(False,))
Head.__doc__ = """One output of a training symbol that the fit loop reads
after a step.  ``find(symbol)`` gives whatever ``emit`` needs to read it
(an output's index, or a tuple that starts with one) or None for a
symbol without it; ``emit(step, handle, outs)`` records the counter
``counter`` from one step's outputs ``outs``, given as the metric gets
them, and is called under the span ``span``; ``always`` says that it is
read while tracing is off too."""


def _op_name(node):
    return None if node.is_variable else getattr(node.op, "name", "")


def counter_head(name, span, counter, columns=(), samples=None, tracks=None,
                 cat="train"):
    """The head that is the ``BlockGrad`` node ``name`` (the output
    ``<name>_output``): its handle is the output's index, and one host
    read of its small array a step gives the samples of ``counter``.
    The array's last axis is ``columns``: a ``(len(columns),)`` array is
    one sample, a ``(n, len(columns))`` array ``n`` of them on the
    tracks ``tracks(node)`` names from the graph (the handle is then
    ``(index, names)``).  ``samples(array)`` gives ``(track, fields)``
    pairs instead where a sample is more than a row under its names."""
    def find(symbol):
        for i, (node, _) in enumerate(symbol._heads):
            if node.name == name and _op_name(node) == "BlockGrad":
                return (i, tracks(node)) if tracks else i
        return None

    def emit(step, handle, outs):
        i, names = handle if tracks else (handle, [None])
        array = outs[i].asnumpy()
        got = samples(array) if samples else zip(names, (
            dict(zip(columns, row.tolist()))
            for row in array.reshape(len(names), -1)))
        for track, fields in got:
            _trace.counter(counter, cat=cat, track=track, **fields)

    return Head(name, span, counter, find, emit)


def _find_load(symbol):
    from ..moe.detect import find_load_heads
    return find_load_heads(symbol)


def _emit_load(step, handle, outs):
    """``MoeStats`` and one ``moe:load`` sample a block (max, mean,
    empty experts, routed, held, dropped) from the ``(blocks, E + 1)``
    load head ``moe.layer.with_load_heads`` groups on.  ``held`` counts
    the routed choices that fell on experts this rank holds (all of
    them where it holds all).  A rank's share also says ``bound``, the
    static row bound its sorted layout is sized by
    (``moe.dispatch.held_rows_bound``, the op's own rule): ``held <=
    bound`` says the step ran the block over ``bound`` rows, and not
    over all that were routed."""
    from ..moe.dispatch import held_rows_bound
    idx, blocks = handle
    for block, row in zip(blocks, outs[idx].asnumpy()):
        counts, dropped = row[:-1], float(row[-1])
        step.moe_stats.note_counts(block, counts, dropped)
        spec = step.moe_blocks[block]
        sample = dict(max=float(counts.max()), mean=float(counts.mean()),
                      empty=int((counts == 0).sum()),
                      routed=float(counts.sum()),
                      held=float(counts[spec.held].sum()), dropped=dropped)
        held = spec.held.stop - spec.held.start
        if held < spec.num_experts:
            sample["bound"] = float(held_rows_bound(
                sample["routed"], spec.num_experts, held))
        _trace.counter(MOE_LOAD.counter, cat="moe", track=block, **sample)


def _find_prediction(symbol):
    """``(main, extra, weight, valid_thresh)`` where ``symbol`` has two
    per-token loss heads, ``MakeLoss`` over ``SoftmaxCELoss``: outputs
    ``main`` (the first such head) and ``extra`` (the second: a
    multi-token-prediction module's), found by what they are and not by
    where they stand; ``weight`` is the second's ``grad_scale``, and
    ``valid_thresh`` its threshold where it normalizes over the rows
    above one (``normalization="valid"``: the positions that have a
    target), else None.  None for a symbol with fewer than two."""
    found = [(i, node.params) for i, (node, _) in enumerate(symbol._heads)
             if _op_name(node) == "MakeLoss"
             and _op_name(node.inputs[0][0]) == "SoftmaxCELoss"]
    if len(found) < 2:
        return None
    (main, _), (extra, p) = found[:2]
    return (main, extra, float(p.grad_scale),
            float(p.valid_thresh) if p.normalization == "valid" else None)


def _emit_prediction(step, handle, outs):
    """One ``mtp:loss`` sample a step: ``main`` the mean of the first
    per-token loss head, ``mtp`` the second head's mean over the
    positions that have a target (as its ``MakeLoss`` normalizes),
    ``weight`` its ``grad_scale``.  Two host reads of ``(rows,)``."""
    main, extra, weight, thresh = handle
    second = outs[extra].asnumpy()
    if thresh is not None:
        second = second[second > thresh]
    _trace.counter(MTP_LOSS.counter, cat="train",
                   main=float(outs[main].asnumpy().mean()),
                   mtp=float(second.mean()) if second.size else 0.0,
                   weight=weight)


def _share_nodes(node):
    """The ``_moe_share_ffn`` nodes under the rows that
    ``moe.layer.with_act_zeros_head`` stacks."""
    stack = node.inputs[0][0]
    rows = [stack] if _op_name(stack) == "Reshape" else \
        [n for n, _ in stack.inputs]
    return [row.inputs[0][0].name for row in rows]


def _exit_samples(sums):
    """``(R + 1,)``, the rows' summed exit probabilities ``p_1 .. p_R``
    and their summed full-depth cross entropy -> the rows' means, and
    ``depth`` the mean exit depth ``sum_t t p_t``."""
    sums = sums.tolist()
    rows = float(sum(sums[:-1])) or 1.0     # a row's p sums to 1
    p = [x / rows for x in sums[:-1]]
    return [(None, dict(ce_last=sums[-1] / rows,
                        depth=sum((t + 1) * x for t, x in enumerate(p)),
                        **{"p%d" % (t + 1): x for t, x in enumerate(p)}))]


def _selection_samples(blocks):
    """``(L, B, 6)``, a block's and sequence's
    ``ops.sparse_attention.STATS`` and index loss -> a sample a block on
    the track ``l<i>``: the counts summed over the batch's sequences,
    ``kl`` the losses' mean."""
    from ..ops.sparse_attention import STATS
    return [("l%d" % l, dict(kl=float(per_seq[:, -1].mean()),
                             **dict(zip(STATS,
                                        per_seq[:, :-1].sum(0).tolist()))))
            for l, per_seq in enumerate(blocks)]


# read while tracing is off too: it feeds ``MoeStats`` as well
MOE_LOAD = Head("moe_load", "fit:moe_load", "moe:load", _find_load,
                _emit_load, always=True)
MTP_LOSS = Head("mtp_loss", "fit:mtp_loss", "mtp:loss", _find_prediction,
                _emit_prediction)
# what the step's labels masked: the positions that have a target, all
# positions, the masked positions' summed 1 / t
DIFFUSION_NOISE = counter_head(
    "diffusion_noise", "fit:diffusion_noise", "diffusion:noise",
    columns=("masked", "positions", "weight_sum"))
# ``zeros`` of the ``lanes`` activated lanes (``act(x Wg)``) of the rows a
# rank really held, a sample a share node
MOE_ACT_ZEROS = counter_head(
    "moe_act_zeros", "fit:moe_act_zeros", "moe:act_zeros",
    columns=("zeros", "lanes"), tracks=_share_nodes, cat="moe")
LOOP_EXIT = counter_head("loop_exit", "fit:loop_exit", "loop:exit",
                         samples=_exit_samples)
DSA_SELECT = counter_head("dsa_select", "fit:dsa_select", "dsa:select",
                          samples=_selection_samples)

HEADS = [MOE_LOAD, MTP_LOSS, DIFFUSION_NOISE, MOE_ACT_ZEROS, LOOP_EXIT,
         DSA_SELECT]


def find_all(symbol):
    """``[(head, handle)]`` for the heads of :data:`HEADS` that
    ``symbol`` carries, in that order."""
    found = ((head, head.find(symbol)) for head in HEADS)
    return [(head, handle) for head, handle in found if handle is not None]
