"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``): a
Qwen3-MoE-shaped pre-norm decoder whose every attention layer SELECTS its
keys by a learned indexer (DeepSeek Sparse Attention, ``sa_config``).

Token embedding -> L x [x + Attn(RMSNorm(x)), x + MoE(RMSNorm(x))] ->
RMSNorm -> untied head, next-token cross entropy.  Attention is
grouped-query (``num_heads`` query heads over ``num_kv_heads`` key/value
heads of ``head_dim``) with an RMSNorm over each head's lanes of q and of
k and rotary embedding whose pairs are shared out over three position
axes (``mrope_sections``: temporal, height, width); with no ``positions``
input all three are the row's index, which is plain rotary.  Beside it
the indexer reads the SAME normed rows behind a ``BlockGrad``:
``index_heads`` queries of ``index_dim`` (``index_q_proj``), one key of
``index_dim`` under a LayerNorm (``index_k_proj``, ``index_k_norm``),
one weight a head (``index_w_proj``), queries and key rotated over all
their lanes by the temporal position.  ``IndexedSelfAttention``
(``ops/sparse_attention.py``) scores every causal pair, keeps a row's
``topk`` best keys and attends over them; its second output, the
sequence's mean ``KL(heads' mean probabilities || softmax of the scores
over the selection)``, is a loss head of its own a layer
(``l<i>_index_loss``).  The two parts of a layer are trained by disjoint
losses: cross entropy and the load balance reach every weight but the
indexer's, the index loss reaches ``index_q_proj``, ``index_k_proj``,
``index_k_norm`` and ``index_w_proj`` and nothing else.

Every MLP is ``num_experts`` SwiGLU experts of ``expert_width``, softmax
over all router logits, the top ``experts_per_tok`` renormalized, no
shared expert; ``experts_held`` > 0 builds one expert-parallel rank's
share (``MoEFeedForward``).

Inputs, through ``Module.fit``'s two default names: ``data`` and
``softmax_label``, both ``(B, T)`` ids; with ``positions`` a third,
``positions`` ``(B, 3, T)``.  The objective is ``mean CE + aux_coef *
sum(load balance) + sum_l mean(index loss)``; every head scales its own
gradient, ``rescale_grad`` is 1.

Outputs, by name: ``lm_output`` the per-token loss (first, where the
metric reads it), one ``*_aux_output`` a block (absent with ``aux_coef``
0), one ``l<i>_index_loss_output`` ``(B,)`` a block, ``moe_load_output``,
and ``dsa_select_output``: ``(L, B, 6)``, a block's and sequence's rows,
selected pairs, causal pairs, tiles hit, causal tiles and index loss, no
gradient, which ``Module.fit`` records as the counter ``dsa:select``
while tracing is on.

Every node of the expert layers is ``force_mirroring``: it keeps its
inputs alone and forms the rest again in the backward pass (what lets
four layers at 8192 rows load beside the selection's passes: 16.50 GiB
by the rule without it, 13.48 with it, ``PERF.md`` §4).

Device scopes: ``attn_proj.l<i>`` (q, k, v, o, head norms, rotation),
``dsa_index.l<i>`` (the indexer's three projections, norm, rotation)
beside the op's own ``dsa_score``, ``dsa_select``, ``dsa_attn``,
``dsa_kl`` ``.l<i>``, ``moe_*.l<i>`` and ``lm_loss``.
"""
from .. import symbol as sym
from ..attribute import AttrScope
from ..initializer import Normal
from ..moe.layer import with_aux_loss, with_load_heads
from ..ops.sparse_attention import STATS
from ..trace.heads import DSA_SELECT
from .decoder import (block, embed, gqa_attention, lm_head_loss, proj,
                      routed_experts, scoped)


# the LayerNorm on the indexer's key (DeepSeek-V3.2-Exp's inference code)
INDEX_EPS = 1e-6


def keye_lm(num_layers, hidden_size, num_heads, num_kv_heads, head_dim,
            index_heads, index_dim, topk, num_experts, experts_per_tok,
            expert_width, vocab_size, seq_len, mrope_sections=(16, 24, 24),
            rope_theta=1e7, rms_eps=1e-6, aux_coef=0.001, experts_held=0,
            first_expert=0, positions=False, embed_sigma=None):
    """The training symbol; see the module docstring.  ``embed_sigma``:
    the embedding starts ``Normal(embed_sigma)`` whatever initializer the
    module is handed (``Variable(init=)``)."""
    if num_heads % num_kv_heads:
        raise ValueError("%d query heads over %d key/value heads"
                         % (num_heads, num_kv_heads))
    if sum(mrope_sections) * 2 != head_dim or index_dim % 2:
        raise ValueError("mrope_sections %r are not the %d pairs of a head, "
                         "or index_dim %d is odd"
                         % (tuple(mrope_sections), head_dim // 2, index_dim))
    where = sym.Variable("positions") if positions else None

    def rotation(sections):
        """``RotaryEmbedding``'s keywords: every axis for the heads'
        sections, the temporal one for the indexer's single section."""
        how = dict(theta=rope_theta, sections=sections)
        if where is None:
            return how
        axes = where if len(sections) == 3 else sym.slice_axis(
            where, axis=1, begin=0, end=1)
        return dict(how, positions=axes, with_positions=True)

    def rotate(x, sections):
        return sym.RotaryEmbedding(x, **rotation(sections))

    selected = []        # (index loss, selection) a block

    def indexed(h, pre, l, q, k, v):
        with scoped("", "dsa_index", l):
            u = sym.BlockGrad(h)
            q_i = rotate(sym.Reshape(
                proj(u, pre + "index_q_proj", index_heads * index_dim),
                shape=(-1, seq_len, index_heads, index_dim)),
                (index_dim // 2,))
            k_i = sym.LayerNorm(proj(u, pre + "index_k_proj", index_dim),
                                eps=INDEX_EPS, name=pre + "index_k_norm")
            k_i = rotate(sym.Reshape(k_i, shape=(-1, seq_len, 1, index_dim)),
                         (index_dim // 2,))
            w_i = sym.Reshape(proj(u, pre + "index_w_proj", index_heads),
                              shape=(-1, seq_len, index_heads))
        a = sym.IndexedSelfAttention(q, k, v, q_i, k_i, w_i, topk=topk,
                                     layer=l, name=pre + "attn")
        selected.append((a[1], a[2]))
        return a[0]

    def experts(h, pre, l):
        with AttrScope(force_mirroring="True"):
            return routed_experts(
                h, pre, l, num_experts, experts_per_tok, expert_width,
                hidden_size, renormalize=True, score="softmax",
                experts_held=experts_held, first_expert=first_expert)

    table = {} if embed_sigma is None else {"weight": sym.Variable(
        "embed_weight", init=Normal(float(embed_sigma)))}
    x = embed(sym.Variable("data"), vocab_size, hidden_size, **table)
    for l in range(num_layers):
        pre = "l%d_" % l
        x = block(
            x, pre, rms_eps,
            lambda h: gqa_attention(
                h, pre, l, seq_len, num_heads, num_kv_heads, head_dim,
                hidden_size, rms_eps,
                rotate=rotation(tuple(mrope_sections)),
                core=lambda q, k, v: indexed(h, pre, l, q, k, v)),
            lambda h: experts(h, pre, l),
            sum_scopes=(scoped("", "attn_proj", l), None), layer=l)
    net = lm_head_loss(x, vocab_size, rms_eps)
    if aux_coef:
        net = with_aux_loss(net, grad_scale=aux_coef)
    heads = [net] + [
        sym.MakeLoss(loss, normalization="batch", name="l%d_index_loss" % l)
        for l, (loss, _) in enumerate(selected)]
    rows = [sym.Reshape(sym.Concat(stats, sym.Reshape(loss, shape=(-1, 1)),
                                   dim=1), shape=(1, -1, len(STATS) + 1))
            for loss, stats in selected]
    counter = rows[0] if len(rows) == 1 else sym.Concat(*rows, dim=0)
    return sym.Group([with_load_heads(sym.Group(heads)),
                      sym.BlockGrad(counter, name=DSA_SELECT.name)])
