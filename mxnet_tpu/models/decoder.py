"""The decoder skeleton: what the LM builders of this package share,
written once.  A decoder is ``embed`` -> L x ``block`` -> ``lm_head_loss``
over ``(B*T, D)`` rows; its builder says which mixer (``gqa_attention``,
``mamba_mixer``, ``latent_attention``, its own) and which MLP (``swiglu``,
``routed_experts``, gated or plain) a layer gets, as functions of the
normed rows, and its head; a model whose layers are ONE branch each (a
norm, a mixer OR an expert layer, a sum: Nemotron-H) stacks
``one_branch_block``.  A further decoder is one builder file over this one,
a configuration, a reference and its tests.  Unnamed nodes are numbered in the order they
are made and a node takes the attribute scope it is made in, so the order
of the statements here is part of every builder's symbol, which
``tests/test_decoder_symbols.py`` holds by its hash.

Every part made of plain ops carries a declared device scope
(``scoped``; ``trace/scopes.py``), so that a device trace tells a block's
parts apart: ``embed``, ``block_norm.l<i>``, ``residual.l<i>``,
``mlp.l<i>``, ``attn_proj.l<i>`` / ``attn_gate.l<i>``, ``lm_head``, beside
the ops' own (``attn``, ``moe_*``, ``lm_loss``).  A scope stands beside a
node that names itself, never around it: of nested declared scopes the
outermost wins.  ``tests/test_block_scopes.py`` fails a builder that
leaves a projection, a norm or a sum generic.
"""
import contextlib

from .. import symbol as sym
from ..attribute import AttrScope
from ..moe.layer import MoEFeedForward


def norm(x, name, eps, groups=0):
    """``RMSNorm`` with a gain; ``groups`` > 0: the statistic over each of
    that many equal parts of the lanes (said only where it is so: an unset
    parameter is not in a node's JSON)."""
    return sym.RMSNorm(x, eps=eps, name=name,
                       **({"groups": groups} if groups else {}))


def proj(x, name, width, bias=False, **inputs):
    return sym.FullyConnected(x, num_hidden=width, no_bias=not bias,
                              name=name, **inputs)


def cut(x, axis, *widths):
    """``x`` cut along ``axis`` into consecutive parts of ``widths``."""
    ends = [sum(widths[:i]) for i in range(len(widths) + 1)]
    return [sym.slice_axis(x, axis=axis, begin=lo, end=hi)
            for lo, hi in zip(ends, ends[1:])]


def scoped(prefix, kind=None, layer=-1):
    """The ``__scope__`` attribute scope (``ops.transformer.node_scope``)
    of one block part, for the device trace.  A part made of plain ops is
    named ``prefix + kind`` (``.l<layer>`` behind it where the block has
    an index): ``mla_q.l3``, ``mtp.eh_proj``.  With no ``kind`` the
    part's ops name their own scope (attention, the expert layer, the
    loss) and take ``prefix`` alone, before it: ``mtp.`` gives
    ``mtp.attn``.  ``prefix`` None (or nothing to say): no attribute."""
    if prefix is None or (kind is None and not prefix):
        return contextlib.nullcontext()
    if kind is None:
        return AttrScope(__scope__=prefix)
    return AttrScope(__scope__=prefix + kind
                     + ("" if layer < 0 else ".l%d" % layer))


def embed(tokens, vocab_size, hidden_size, name="embed", scope="", **inputs):
    """Ids ``(B, T)`` -> rows ``(B*T, D)``; ``weight=`` shares a table.
    Scope: ``scope + "embed"`` (the table's gradient with it); ``scope``
    None sets none, for a caller whose own scope is around the call."""
    with scoped(scope, "embed"):
        x = sym.Embedding(tokens, input_dim=vocab_size,
                          output_dim=hidden_size, name=name, **inputs)
        return sym.Reshape(x, shape=(-1, hidden_size))


def swiglu(h, pre, width, hidden_size, layer=-1, scope=""):
    """The dense MLP: ``(silu(h Wg) * (h Wu)) Wd``.  Scope: ``scope +
    "mlp"``, ``.l<layer>`` behind it."""
    with scoped(scope, "mlp", layer):
        gate = sym.Activation(proj(h, pre + "gate_proj", width),
                              act_type="silu")
        return proj(gate * proj(h, pre + "up_proj", width),
                    pre + "down_proj", hidden_size)


def routed_experts(h, pre, layer, num_experts, experts_per_tok, expert_width,
                   hidden_size=0, act_type="silu", gated=True, **router):
    """The routed expert layer ``pre + "moe"``: gated experts (SwiGLU;
    ReGLU with ``act_type="relu"``) or, ``gated`` off, plain ones of two
    matrices (``act(x W1) W2``; the squared ReLU is ``act_type="relu2"``),
    a shared expert in the same form; no bias, no token-choice dropped.
    ``layer`` < 0: no trace index.  ``hidden_size``: the output's width,
    which a shared expert needs said (0: the input's, left to the op).
    ``router``: the router's kind, the rows it reads where they are not
    ``h`` (``router_data``) and a rank's share, as ``MoEFeedForward``
    names them."""
    return MoEFeedForward(
        h, num_hidden=expert_width, num_experts=num_experts,
        k=experts_per_tok, capacity_factor=0.0, name=pre + "moe",
        act_type=act_type, gated=gated, no_bias=True,
        layer=None if layer < 0 else layer, output_dim=hidden_size, **router)


def gqa_attention(h, pre, layer, rows, num_heads, num_kv_heads, head_dim,
                  hidden_size, eps, rotate=None, gated=False,
                  head_norms=True, core=None, **mask):
    """Grouped-query attention with an RMSNorm over each head's lanes of
    q and of k (none with ``head_norms`` off), ``(B*rows, D)`` ->
    ``(B*rows, D)``.  ``rotate`` places q and k (default: no positions
    but the order): ``RotaryEmbedding``'s keywords (``theta``, ``period``,
    ``sections``, ``positions`` with ``with_positions``), and norm and
    rotation are ONE node ``HeadNormRotary`` on the rows as the
    projection writes them, reshaped to heads only in front of the core
    op (``ops/head_rotary.py``); or a function of the ``(B, rows, H,
    head_dim)`` heads, which then stand behind a ``Reshape`` and an
    ``RMSNorm`` of their own.  ``mask`` is
    ``CausalSelfAttention``'s (default: causal), ``gated`` multiplies the
    heads' outputs by the sigmoid of a gate before ``o_proj``: True, the
    gate is its own projection ``h Wg``; ``"query"``, it is the second
    half of a doubled ``q_proj``, head by head ``[q | gate]`` (its halves
    are cut from the heads, so q and k keep the nodes over the heads).
    ``core``:
    another core op than ``CausalSelfAttention``, a function of the
    placed ``(q, k, v)`` that gives the heads' outputs.  Scopes:
    ``attn_proj.l<i>``, ``attn_gate.l<i>``."""
    width = num_heads * head_dim
    on_rows = not callable(rotate) and gated != "query" \
        and bool(head_norms or rotate)

    def heads(x, n, lanes=head_dim):
        return sym.Reshape(x, shape=(-1, rows, n, lanes))

    def projected(name, n, lanes=head_dim):
        """A projection's rows where q and k are placed on them, else
        its heads."""
        x = proj(h, pre + name + "_proj", n * lanes)
        return x if on_rows else heads(x, n, lanes)

    def placed(x, name, n):
        if on_rows:
            how = dict(rotate, seq_len=rows) if rotate else {}
            return heads(sym.HeadNormRotary(
                x, head_dim=head_dim, norm=head_norms, eps=eps,
                name=pre + name + ("_norm" if head_norms else "_rotary"),
                **how), n)
        if head_norms:
            x = norm(x, pre + name + "_norm", eps)
        if callable(rotate):
            return rotate(x)
        return sym.RotaryEmbedding(x, **rotate) if rotate else x

    with scoped("", "attn_proj", layer):
        q = projected("q", num_heads,
                      head_dim * (2 if gated == "query" else 1))
        if gated == "query":
            q, gate = (sym.slice_axis(q, axis=3, begin=lo, end=lo + head_dim)
                       for lo in (0, head_dim))
        q = placed(q, "q", num_heads)
        k = placed(projected("k", num_kv_heads), "k", num_kv_heads)
        v = projected("v", num_kv_heads)
        if on_rows:
            v = heads(v, num_kv_heads)
    a = core(q, k, v) if core else sym.CausalSelfAttention(
        q, k, v, layer=layer, name=pre + "attn", **mask)
    with scoped("", "attn_gate" if gated else "attn_proj", layer):
        a = sym.Reshape(a, shape=(-1, width))
        if gated:
            gate = sym.Reshape(gate, shape=(-1, width)) if gated == "query" \
                else proj(h, pre + "attn_gate_proj", width)
            a = a * sym.Activation(gate, act_type="sigmoid")
    with scoped("", "attn_proj", layer):
        return proj(a, pre + "o_proj", hidden_size)


def mamba_mixer(h, pre, layer, rows, hidden_size, heads, head_dim, state,
                groups, conv_kernel, eps, norm_groups=0):
    """The Mamba-2 mixer (arXiv:2405.21060), ``(B*rows, D)`` -> ``(B*rows,
    D)``: ``[z | xBC | dt] = h W_in`` (``heads * head_dim`` | that + ``2
    groups state`` | ``heads`` wide, in that order); ``xBC = silu(conv(xBC)
    + b)``, a depthwise causal convolution of ``conv_kernel`` taps with a
    bias; ``[x | B | C] = xBC``, ``B`` and ``C`` in ``groups`` groups of
    ``state`` lanes, head ``j`` reading group ``j // (heads / groups)``;
    the scan ``SSDScan`` (``ops/ssd.py``); ``y = RMSNorm(y * silu(z))``
    with one gain over all lanes, the statistic over all of them or, with
    ``norm_groups``, over each of that many equal parts; ``y W_out``.  No
    projection bias.  Scopes: ``ssm_proj.l<i>`` (both projections),
    ``ssm_conv.l<i>``, the scan's own ``ssm_scan.l<i>``, ``ssm_norm.l<i>``
    (the gate and the norm)."""
    inner, bc = heads * head_dim, groups * state
    with scoped("", "ssm_proj", layer):
        z, xbc, dt = cut(proj(h, pre + "in_proj", 2 * inner + 2 * bc + heads),
                         1, inner, inner + 2 * bc, heads)
    with scoped("", "ssm_conv", layer):
        xbc = sym.CausalConv1D(
            sym.Reshape(xbc, shape=(-1, rows, inner + 2 * bc)),
            kernel=conv_kernel, act_type="silu", no_bias=False,
            name=pre + "conv")
        x, b, c = (sym.Reshape(part, shape=(-1, rows, n, lanes))
                   for part, n, lanes in zip(
                       cut(xbc, 2, inner, bc, bc), (heads, groups, groups),
                       (head_dim, state, state)))
    y = sym.SSDScan(x, b, c, sym.Reshape(dt, shape=(-1, rows, heads)),
                    layer=layer, name=pre + "ssm")
    with scoped("", "ssm_norm", layer):
        y = norm(sym.Reshape(y, shape=(-1, inner))
                 * sym.Activation(z, act_type="silu"),
                 pre + "ssm_norm", eps, norm_groups)
    with scoped("", "ssm_proj", layer):
        return proj(y, pre + "out_proj", hidden_size)


LAYER_KINDS = ("sliding", "full")


def layer_kinds(layer_types, num_layers, kinds=LAYER_KINDS):
    """``layer_types`` as a list, one of ``kinds`` for each of the
    ``num_layers`` layers BUILT: by default ``LAYER_KINDS``, of a model
    that mixes sliding-window and full attention; a builder whose mixers
    are of other kinds names its own."""
    layer_types = list(layer_types)
    if len(layer_types) != num_layers \
            or any(kind not in kinds for kind in layer_types):
        raise ValueError("layer_types %r: %d layers, each one of %s"
                         % (layer_types, num_layers, tuple(kinds)))
    return layer_types


def kind_attention(h, pre, layer, kind, window, rope_theta, *sizes, **how):
    """``gqa_attention`` of one layer of such a model.  The kind is the
    op's mask and whether the heads are rotated, nothing else: a
    ``sliding`` layer rotates q and k (``rope_theta``, half-split
    pairing) and reads under ``CausalSelfAttention``'s ``sliding_window``
    mask of ``window``; a ``full`` layer rotates NOTHING (it has no
    positions but the causal order) and reads under the causal mask.
    ``sizes`` and ``how`` are ``gqa_attention``'s, from ``rows`` on."""
    if kind == "sliding":
        how = dict(how, mask="sliding_window", window=window,
                   rotate=dict(theta=rope_theta))
    return gqa_attention(h, pre, layer, *sizes, **how)


def block(x, pre, eps, mixer, mlp, mixer_norm="attn_norm",
          post_norms=(None, None), sum_scopes=(None, None),
          mlp_sees_mixer_rows=False, layer=-1):
    """One residual block: ``x + [post](mixer(norm(x)))``, then
    ``x + [post](mlp(norm(x)))``.  ``mixer`` and ``mlp`` are functions of
    the normed rows; ``post_norms`` names the two norms inside the
    branches of a sandwich block.  ``sum_scopes``: the scope a sum is
    made in (``scoped``), where a builder's symbol has one there.
    ``mlp_sees_mixer_rows``: ``mlp`` is a function of its own normed rows
    and, second, of the rows the mixer read (a router placed before the
    mixer).  ``layer``: the block's index in its model, for the scopes of
    its own nodes: ``block_norm.l<layer>`` (every norm here) and
    ``residual.l<layer>`` (a sum ``sum_scopes`` names no scope for)."""
    normed = []
    for branch, pre_norm, post_norm, scope in zip(
            (mixer, mlp), (mixer_norm, "ffn_norm"), post_norms, sum_scopes):
        with scoped("", "block_norm", layer):
            normed.append(norm(x, pre + pre_norm, eps))
        y = branch(*reversed(normed)) \
            if mlp_sees_mixer_rows and branch is mlp else branch(normed[-1])
        if post_norm:
            with scoped("", "block_norm", layer):
                y = norm(y, pre + post_norm, eps)
        with scope or scoped("", "residual", layer):
            x = x + y
    return x


def one_branch_block(x, pre, eps, branch, layer=-1):
    """One residual layer of ONE branch: ``x + branch(norm(x))``, the norm
    ``pre + "norm"`` under ``block_norm.l<layer>``, the sum under
    ``residual.l<layer>``.  ``branch`` is a function of the normed rows: a
    mixer or an MLP, whichever the layer is."""
    with scoped("", "block_norm", layer):
        h = norm(x, pre + "norm", eps)
    y = branch(h)
    with scoped("", "residual", layer):
        return x + y


def lm_head_loss(x, vocab_size, eps, label=None, head_weight=None,
                 row_weight=None, logits_divisor=None, **ignoring):
    """Rows ``(B*T, D)`` -> ``final_norm`` -> ``lm_head`` -> the per-row
    cross entropy -> the loss head ``lm``, whose gradient is 1 / rows.
    ``label`` None: ``softmax_label`` ``(B, T)``, flattened.
    ``head_weight`` shares the head's matrix, ``row_weight`` multiplies
    the rows' losses, ``logits_divisor`` divides the logits before the
    loss, ``ignoring`` is ``SoftmaxCELoss``'s.  Scopes: ``lm_head`` (the
    norm, the projection, the division), the loss node's own ``lm_loss``."""
    if label is None:
        label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    head = {} if head_weight is None else {"weight": head_weight}
    # not around the loss node: the outermost declared scope wins, and
    # the loss's operations are ``lm_loss``'s
    with scoped("", "lm_head"):
        logits = proj(norm(x, "final_norm", eps), "lm_head", vocab_size,
                      **head)
        if logits_divisor is not None:
            logits = logits / logits_divisor
    rows = sym.SoftmaxCELoss(logits, label, name="lm_loss", **ignoring)
    if row_weight is not None:
        rows = rows * row_weight
    return sym.MakeLoss(rows, normalization="batch", name="lm")
