"""Symbol docstring helpers (reference python/mxnet/symbol_doc.py: extra
doc sections attached to auto-generated symbol constructors).

Constructors here are generated from the op registry
(mxnet_tpu/ops/registry.py), which carries the dmlc::Parameter-style
schemas; this module supplies the same supplementary-documentation hook."""
from __future__ import annotations

__all__ = ["SymbolDoc", "get_output_shape"]


class SymbolDoc(object):
    """Base for per-op documentation supplements (reference SymbolDoc).
    Subclass with the op name + 'Doc' and a docstring; `build_doc` merges
    it into the generated constructor's __doc__."""

    @staticmethod
    def get_output_shape(sym, **input_shapes):
        """Infer and return {output_name: shape} — the doc-example helper
        the reference exposes for interactive exploration."""
        _, s_outputs, _ = sym.infer_shape(**input_shapes)
        return dict(zip(sym.list_outputs(), s_outputs))


def get_output_shape(sym, **input_shapes):
    return SymbolDoc.get_output_shape(sym, **input_shapes)


# -- the transformer block's ops (ops/transformer.py, ops/moe.py) ------------

class RMSNormDoc(SymbolDoc):
    """``data / sqrt(mean(data**2, last axis) + eps) * gamma``; the
    statistics in float32 whatever the data's dtype.  ``gamma`` has the
    last axis' length and initializes to 1 (the ``*gamma`` name rule).

    >>> x = mx.sym.RMSNorm(mx.sym.Variable("data"), eps=1e-5, name="n")
    >>> SymbolDoc.get_output_shape(x, data=(8, 128, 2048))
    {'n_output': (8, 128, 2048)}
    """


class RotaryEmbeddingDoc(SymbolDoc):
    """Rotary position embedding of ``(batch, seq, heads, head_dim)`` at
    positions ``0..seq-1``: dimension ``i`` pairs with ``i + head_dim/2``
    (the half-split of the llama/olmoe modelling code), angle
    ``pos * theta**(-2i/head_dim)``."""


class CausalSelfAttentionDoc(SymbolDoc):
    """``softmax(q k^T * scale + causal mask) v`` per head over
    ``(batch, seq, heads, head_dim)`` query, key, value; ``scale`` 0
    means ``head_dim**-0.5``.  The softmax runs in float32 and the
    ``(batch, heads, seq, seq)`` scores are never held whole: queries
    go in blocks whose scores are recomputed in the backward pass.

    >>> q, k, v = (mx.sym.Variable(n) for n in ("q", "k", "v"))
    >>> a = mx.sym.CausalSelfAttention(q, k, v, name="attn")
    >>> SymbolDoc.get_output_shape(a, q=(4, 4096, 16, 128))
    {'attn_output': (4, 4096, 16, 128)}
    """


class SoftmaxCELossDoc(SymbolDoc):
    """Per-row cross-entropy of ``(rows, classes)`` logits against
    integer labels ``(rows,)``: the LOSS, float32 ``(rows,)``, not the
    probabilities ``SoftmaxOutput`` returns, so a metric reads ``rows``
    numbers a step.  Differentiable; train on it through ``MakeLoss``:

    >>> loss = mx.sym.SoftmaxCELoss(logits, labels)
    >>> head = mx.sym.MakeLoss(loss, normalization="batch")   # mean CE
    >>> metric = mx.metric.OutputMean(0)
    """


class _moe_expert_ffnDoc(SymbolDoc):
    """Per-expert feed-forward over what ``_moe_dispatch`` emits: the
    ``(E, C, D)`` capacity buckets (batched einsums) or, when the
    dispatch node's ``capacity_factor <= 0``, the ``(T*k, D)`` rows
    sorted by expert (grouped matmuls whose group sizes are
    ``counts``).  ``gated=True`` is ``(act(x Wg) * (x W1)) W2``: SwiGLU
    with ``act_type="silu"``.  ``counts`` is the last input; left out,
    it is taken from the dispatch node that feeds ``data``.  Built by
    ``mx.moe.MoEFeedForward``."""


class _moe_share_ffnDoc(SymbolDoc):
    """One expert-parallel rank's share of a drop-free routed layer:
    gather, expert FFN and combine in one node over ``data`` (T, D) and
    the ``weight``, ``slot``, ``order`` and ``counts`` of the
    ``_moe_dispatch`` node that routed it (``experts_held > 0`` on
    both).  Its sorted-row passes run over a static bound on the rows the
    rank holds, ``moe.dispatch.held_rows_bound(T*k, E, experts_held)``,
    and once more over the rows behind it in a step whose held rows
    pass the bound: nothing is dropped.  Built by ``mx.moe.MoEFeedForward``."""


def build_doc(func_name: str, desc: str, arg_names, arg_types, arg_descs,
              key_var_num_args: str = "", ret_type: str = "Symbol"):
    """Assemble a numpy-style docstring from registry metadata (reference
    symbol_doc.py _build_doc used by the generated ctors)."""
    lines = [desc, "", "Parameters", "----------"]
    for name, typ, d in zip(arg_names, arg_types, arg_descs):
        lines.append("%s : %s" % (name, typ))
        if d:
            lines.append("    %s" % d)
    if key_var_num_args:
        lines += ["%s : int, optional" % key_var_num_args,
                  "    number of variadic inputs"]
    lines += ["name : string, optional", "    Name of the resulting symbol.",
              "", "Returns", "-------", "%s" % ret_type,
              "    The result symbol."]
    return "\n".join(lines)
