"""Multi-head latent attention (MLA, DeepSeek-V2 arXiv:2405.04434) as
symbols: ONE assembly for every decoder of this package that mixes by it
(``kimi_linear``: plain queries, no positions; ``glm_moe_lite``:
compressed queries, rotary on the rope parts).

Keys and values are decompressed from one ``kv_lora_rank`` latent
(RMSNorm'ed) a token plus one ``qk_rope_dim`` key part shared by all
heads; queries are ``qk_nope_dim + qk_rope_dim`` a head, values
``v_head_dim``.  ``q_lora_rank`` > 0 compresses the queries through a
normed low-rank pair (``q_a_proj`` -> ``q_a_norm`` -> ``q_b_proj``), 0
projects them in one step (``q_proj``).  ``rope_theta`` > 0 rotates the
rope part of every query head and the shared key part at positions
``0..T-1`` (``RotaryEmbedding``: half-split pairing, all of the part's
lanes), 0 leaves both plain projections.  The softmax scale is
``(qk_nope_dim + qk_rope_dim) ** -0.5`` (``CausalSelfAttention``'s own).
"""
from .. import symbol as sym
from .decoder import norm, proj, scoped


def latent_attention(h, pre, seq_len, hidden_size, heads, kv_lora_rank,
                     qk_nope_dim, qk_rope_dim, v_head_dim, rms_eps,
                     layer=-1, q_lora_rank=0, rope_theta=0.0, scope=None):
    """``h`` ``(B*T, hidden)`` -> ``(B*T, hidden)``; parameters are named
    ``pre`` + ``q_proj`` (or ``q_a_proj``, ``q_a_norm``, ``q_b_proj``),
    ``kv_a_proj``, ``kv_a_norm``, ``kv_b_proj``, ``o_proj``.  ``layer``
    names the attention op's trace scope.  ``scope`` is the prefix of
    the device scopes of the parts made of plain ops (``scoped``:
    ``mla_q``, ``mla_kv``, ``rope``; ``""`` for a trunk's block,
    ``"mtp."`` inside a prediction module; the rotated queries' cut and
    the output projection are ``attn_proj`` under either); None sets no
    attribute and leaves the symbol as it was before scopes."""
    def rotate(x):
        with scoped(scope, "rope", layer):
            return sym.RotaryEmbedding(x, theta=rope_theta)

    # with no prefix: what a prediction module made outside its ``mtp.``
    # scopes stays outside the kind, and ``scope_mtp_ms`` reads what it did
    outside = None if scope is None else ""
    qk_dim = qk_nope_dim + qk_rope_dim
    with scoped(scope, "mla_q", layer):
        q = proj(norm(proj(h, pre + "q_a_proj", q_lora_rank),
                      pre + "q_a_norm", rms_eps),
                 pre + "q_b_proj", heads * qk_dim) \
            if q_lora_rank else proj(h, pre + "q_proj", heads * qk_dim)
        q = sym.Reshape(q, shape=(-1, seq_len, heads, qk_dim))
    if rope_theta:
        with scoped(outside, "attn_proj", layer):
            q = sym.Concat(
                sym.slice_axis(q, axis=3, begin=0, end=qk_nope_dim),
                rotate(sym.slice_axis(q, axis=3, begin=qk_nope_dim,
                                      end=qk_dim)),   # its own, inside this
                dim=3)
    with scoped(scope, "mla_kv", layer):
        kv_a = proj(h, pre + "kv_a_proj", kv_lora_rank + qk_rope_dim)
        latent = norm(sym.slice_axis(kv_a, axis=1, begin=0,
                                     end=kv_lora_rank), pre + "kv_a_norm",
                      rms_eps)
        k_shared = sym.Reshape(
            sym.slice_axis(kv_a, axis=1, begin=kv_lora_rank,
                           end=kv_lora_rank + qk_rope_dim),
            shape=(-1, seq_len, 1, qk_rope_dim))
        kv = sym.Reshape(
            proj(latent, pre + "kv_b_proj",
                 heads * (qk_nope_dim + v_head_dim)),
            shape=(-1, seq_len, heads, qk_nope_dim + v_head_dim))
        if rope_theta:
            k_shared = rotate(k_shared)     # its own scope, inside this
        k = sym.Concat(
            sym.slice_axis(kv, axis=3, begin=0, end=qk_nope_dim),
            sym.broadcast_axis(k_shared, axis=2, size=heads), dim=3)
        v = sym.slice_axis(kv, axis=3, begin=qk_nope_dim,
                           end=qk_nope_dim + v_head_dim)
    with scoped(scope):
        a = sym.CausalSelfAttention(q, k, v, layer=layer, name=pre + "attn")
    with scoped(outside, "attn_proj", layer):
        return proj(sym.Reshape(a, shape=(-1, heads * v_head_dim)),
                    pre + "o_proj", hidden_size)
