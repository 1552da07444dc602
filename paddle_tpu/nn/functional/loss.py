"""Loss functionals (analog of python/paddle/nn/functional/loss.py).

All losses are registry-routed (op_body/op_call, core/dispatch.py) so
``override_kernel`` reaches them like PD_REGISTER_KERNEL replacements do in
the reference (paddle/phi/core/kernel_registry.h:196). Optional tensor
inputs (class weights, normalizers) ride as trailing positional arrays.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.dispatch import op_body, op_call
from ...core.tensor import Tensor


def _reduce_arr(loss, reduction):
    if reduction == "mean":
        return loss.mean()
    if reduction == "sum":
        return loss.sum()
    return loss


@op_body("cross_entropy")
def _cross_entropy(logits, lbl, *maybe_w, axis, ignore_index, reduction,
                   soft_label, use_softmax, label_smoothing):
    """Softmax cross entropy (reference: python/paddle/nn/functional/loss.py
    cross_entropy; SPMD-parallel variant lives in distributed mp_layers)."""
    ax = axis % logits.ndim
    logp = jax.nn.log_softmax(logits, axis=ax) if use_softmax else jnp.log(
        jnp.maximum(logits, 1e-30))
    if soft_label or (lbl.ndim == logits.ndim and lbl.shape == logits.shape):
        soft = lbl
        if label_smoothing > 0:
            n = logits.shape[ax]
            soft = soft * (1 - label_smoothing) + label_smoothing / n
        loss = -(soft * logp).sum(axis=ax)
    else:
        lbl_ = lbl
        if lbl_.ndim == logits.ndim:  # trailing 1 dim
            lbl_ = jnp.squeeze(lbl_, axis=ax)
        valid = lbl_ != ignore_index
        safe = jnp.where(valid, lbl_, 0).astype(jnp.int32)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, ax), axis=ax)
        picked = jnp.squeeze(picked, axis=ax)
        if label_smoothing > 0:
            smooth_loss = -logp.mean(axis=ax)
            loss = -(1 - label_smoothing) * picked + label_smoothing * smooth_loss
        else:
            loss = -picked
        if maybe_w:
            w = maybe_w[0][safe]
            loss = loss * w
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            denom = (jnp.sum(maybe_w[0][safe] * valid) if maybe_w
                     else jnp.maximum(valid.sum(), 1))
            return loss.sum() / denom
    return _reduce_arr(loss, reduction)


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0,
                  name=None):
    args = [input, label] + ([weight] if weight is not None else [])
    return op_call("cross_entropy", _cross_entropy, *args, axis=axis,
                   ignore_index=ignore_index, reduction=reduction,
                   soft_label=bool(soft_label), use_softmax=bool(use_softmax),
                   label_smoothing=label_smoothing)


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False, axis=-1):
    """``numeric_stable_mode`` is accepted for parity and has no effect:
    the lowering is always the stable log-sum-exp form (the reference flag
    selects between its two CUDA kernels)."""
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    if return_softmax:
        from .activation import softmax
        return loss, softmax(logits, axis=axis)
    return loss


@op_body("nll_loss")
def _nll_loss(logp, lbl, *maybe_w, ignore_index, reduction):
    valid = lbl != ignore_index
    safe = jnp.where(valid, lbl, 0).astype(jnp.int32)
    picked = jnp.take_along_axis(logp, safe[:, None] if logp.ndim == 2 else
                                 jnp.expand_dims(safe, 1), axis=1)
    loss = -jnp.squeeze(picked, axis=1)
    if maybe_w:
        loss = loss * maybe_w[0][safe]
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        denom = jnp.sum(maybe_w[0][safe] * valid) if maybe_w else jnp.maximum(valid.sum(), 1)
        return loss.sum() / denom
    return _reduce_arr(loss, reduction)


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    args = [input, label] + ([weight] if weight is not None else [])
    return op_call("nll_loss", _nll_loss, *args, ignore_index=ignore_index,
                   reduction=reduction)


@op_body("mse_loss")
def _mse_loss(a, b, *, reduction):
    return _reduce_arr(jnp.square(a - b), reduction)


def mse_loss(input, label, reduction="mean", name=None):
    return op_call("mse_loss", _mse_loss, input, label, reduction=reduction)


@op_body("l1_loss")
def _l1_loss(a, b, *, reduction):
    return _reduce_arr(jnp.abs(a - b), reduction)


def l1_loss(input, label, reduction="mean", name=None):
    return op_call("l1_loss", _l1_loss, input, label, reduction=reduction)


@op_body("smooth_l1_loss")
def _smooth_l1_loss(a, b, *, reduction, delta):
    d = jnp.abs(a - b)
    loss = jnp.where(d < delta, 0.5 * d * d, delta * (d - 0.5 * delta))
    return _reduce_arr(loss, reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    return op_call("smooth_l1_loss", _smooth_l1_loss, input, label,
                   reduction=reduction, delta=delta)


def huber_loss(input, label, delta=1.0, reduction="mean", name=None):
    return smooth_l1_loss(input, label, reduction, delta)


@op_body("bce")
def _bce(p, y, *maybe_w, reduction):
    p = jnp.clip(p, 1e-12, 1 - 1e-7)
    loss = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
    if maybe_w:
        loss = loss * maybe_w[0]
    return _reduce_arr(loss, reduction)


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    args = [input, label] + ([weight] if weight is not None else [])
    return op_call("bce", _bce, *args, reduction=reduction)


@op_body("bce_with_logits")
def _bce_with_logits(z, y, *rest, has_weight, has_pos_weight, reduction):
    i = 0
    w = pw = None
    if has_weight:
        w = rest[i]
        i += 1
    if has_pos_weight:
        pw = rest[i]
    # stable: max(z,0) - z*y + log(1+exp(-|z|)), with pos_weight on the y term
    if pw is not None:
        log_w = (pw - 1) * y + 1
        loss = (1 - y) * z + log_w * (jnp.logaddexp(0.0, -jnp.abs(z)) + jnp.maximum(-z, 0.0))
    else:
        loss = jnp.maximum(z, 0) - z * y + jnp.logaddexp(0.0, -jnp.abs(z))
    if w is not None:
        loss = loss * w
    return _reduce_arr(loss, reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None, name=None):
    args = [logit, label] + [t for t in (weight, pos_weight) if t is not None]
    return op_call("bce_with_logits", _bce_with_logits, *args,
                   has_weight=weight is not None,
                   has_pos_weight=pos_weight is not None, reduction=reduction)


@op_body("kl_div")
def _kl_div(logp, q, *, reduction, log_target):
    if log_target:
        loss = jnp.exp(q) * (q - logp)
    else:
        loss = q * (jnp.log(jnp.maximum(q, 1e-30)) - logp)
    if reduction == "batchmean":
        return loss.sum() / logp.shape[0]
    return _reduce_arr(loss, reduction)


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    return op_call("kl_div", _kl_div, input, label, reduction=reduction,
                   log_target=bool(log_target))


@op_body("margin_ranking_loss")
def _margin_ranking_loss(a, b, y, *, margin, reduction):
    return _reduce_arr(jnp.maximum(0.0, -y * (a - b) + margin), reduction)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    return op_call("margin_ranking_loss", _margin_ranking_loss, input, other,
                   label, margin=margin, reduction=reduction)


@op_body("cosine_embedding_loss")
def _cosine_embedding_loss(a, b, y, *, margin, reduction):
    cos = (a * b).sum(-1) / (jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1) + 1e-12)
    loss = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
    return _reduce_arr(loss, reduction)


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean", name=None):
    return op_call("cosine_embedding_loss", _cosine_embedding_loss, input1,
                   input2, label, margin=margin, reduction=reduction)


@op_body("triplet_margin_loss")
def _triplet_margin_loss(a, pos, neg, *, margin, p, epsilon, swap, reduction):
    dp = jnp.linalg.norm(a - pos + epsilon, ord=p, axis=-1)
    dn = jnp.linalg.norm(a - neg + epsilon, ord=p, axis=-1)
    if swap:
        dn2 = jnp.linalg.norm(pos - neg + epsilon, ord=p, axis=-1)
        dn = jnp.minimum(dn, dn2)
    return _reduce_arr(jnp.maximum(dp - dn + margin, 0.0), reduction)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0, epsilon=1e-6,
                        swap=False, reduction="mean", name=None):
    return op_call("triplet_margin_loss", _triplet_margin_loss, input,
                   positive, negative, margin=margin, p=p, epsilon=epsilon,
                   swap=bool(swap), reduction=reduction)


@op_body("hinge_embedding_loss")
def _hinge_embedding_loss(a, y, *, margin, reduction):
    loss = jnp.where(y == 1, a, jnp.maximum(0.0, margin - a))
    return _reduce_arr(loss, reduction)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return op_call("hinge_embedding_loss", _hinge_embedding_loss, input,
                   label, margin=margin, reduction=reduction)


@op_body("square_error_cost")
def _square_error_cost(a, b):
    return jnp.square(a - b)


def square_error_cost(input, label):
    return op_call("square_error_cost", _square_error_cost, input, label)


@op_body("sigmoid_focal_loss")
def _sigmoid_focal_loss(z, y, *maybe_n, alpha, gamma, reduction):
    p = jax.nn.sigmoid(z)
    ce = jnp.maximum(z, 0) - z * y + jnp.logaddexp(0.0, -jnp.abs(z))
    p_t = p * y + (1 - p) * (1 - y)
    a_t = alpha * y + (1 - alpha) * (1 - y)
    loss = a_t * ((1 - p_t) ** gamma) * ce
    if maybe_n:
        loss = loss / maybe_n[0]
    return _reduce_arr(loss, reduction)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    args = [logit, label] + ([normalizer] if normalizer is not None else [])
    return op_call("sigmoid_focal_loss", _sigmoid_focal_loss, *args,
                   alpha=alpha, gamma=gamma, reduction=reduction)


@op_body("ctc_loss")
def _ctc_loss(lp, lbl, in_len, lbl_len, *, blank, reduction,
              norm_by_times=False):
    """CTC via the dynamic-programming forward algorithm in pure lax
    (reference: paddle/phi/kernels/gpu/warpctc_kernel.cu → here an XLA scan)."""
    import jax.lax as lax

    # lp: [T, B, C] log-probs; lbl: [B, S]
    T, B, C = lp.shape
    S = lbl.shape[1]
    ext = jnp.full((B, 2 * S + 1), blank, dtype=lbl.dtype)
    ext = ext.at[:, 1::2].set(lbl)  # blank-interleaved
    L = 2 * S + 1
    neg_inf = -1e30
    alpha0 = jnp.full((B, L), neg_inf)
    alpha0 = alpha0.at[:, 0].set(lp[0, :, blank])
    alpha0 = alpha0.at[:, 1].set(jnp.take_along_axis(lp[0], ext[:, 1:2], axis=1)[:, 0])

    same_as_prev2 = jnp.pad(ext[:, 2:] == ext[:, :-2], ((0, 0), (2, 0)),
                            constant_values=True)

    def step(alpha, lp_t):
        a1 = jnp.pad(alpha[:, :-1], ((0, 0), (1, 0)), constant_values=neg_inf)
        a2 = jnp.pad(alpha[:, :-2], ((0, 0), (2, 0)), constant_values=neg_inf)
        a2 = jnp.where(same_as_prev2, neg_inf, a2)
        merged = jnp.logaddexp(jnp.logaddexp(alpha, a1), a2)
        emit = jnp.take_along_axis(lp_t, ext, axis=1)
        new_alpha = merged + emit
        return new_alpha, new_alpha

    _, alphas = lax.scan(step, alpha0, lp[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # [T, B, L]
    t_idx = (in_len - 1).astype(jnp.int32)
    final = alphas[t_idx, jnp.arange(B)]  # [B, L]
    end1 = 2 * lbl_len.astype(jnp.int32)
    end2 = 2 * lbl_len.astype(jnp.int32) - 1
    ll = jnp.logaddexp(
        jnp.take_along_axis(final, end1[:, None], axis=1)[:, 0],
        jnp.take_along_axis(final, jnp.maximum(end2, 0)[:, None], axis=1)[:, 0])
    loss = -ll
    if norm_by_times:
        # reference warpctc norm_by_times: scale each sequence's loss by
        # its number of time steps
        loss = loss / jnp.maximum(in_len.astype(loss.dtype), 1)
    if reduction == "mean":
        return (loss / jnp.maximum(lbl_len, 1)).mean()
    return _reduce_arr(loss, reduction)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    return op_call("ctc_loss", _ctc_loss, log_probs, labels, input_lengths,
                   label_lengths, blank=blank, reduction=reduction,
                   norm_by_times=bool(norm_by_times))


@op_body("fused_linear_cross_entropy")
def _fused_linear_cross_entropy(h, w, lbl, *, chunk_size, transpose_weight,
                                reduction, ignore_index):
    from jax import lax

    # chunks run along the LAST token axis: [tokens] flat, or
    # [batch, seq] with chunk_size // batch positions a row, so a chunk
    # holds chunk_size tokens of the whole batch and never crosses a row
    *rows, n, d = h.shape
    chunk = min(max(1, chunk_size // math.prod(rows)), n)
    pad = (-n) % chunk
    if pad:  # pad to a chunk multiple with ignored labels (no divisor
        # search: a prime token count must not degrade to chunk=1)
        h = jnp.pad(h, [(0, 0)] * len(rows) + [(0, pad), (0, 0)])
        lbl = jnp.pad(lbl, [(0, 0)] * len(rows) + [(0, pad)],
                      constant_values=ignore_index)
        n = n + pad

    def chunk_loss(h_c, l_c):
        # a chunk's rows as one token axis: the matmul [chunk_size, d]
        # the flat form has (batch leads, so a split of it stays whole)
        h_c, l_c = h_c.reshape(-1, d), l_c.reshape(-1)
        logits = (h_c @ w.T if transpose_weight else h_c @ w)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = l_c != ignore_index
        safe = jnp.where(valid, l_c, 0).astype(jnp.int32)
        gold = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        tok = jnp.where(valid, lse - gold, 0.0)
        return tok.sum(), valid.sum()

    # [n_chunks, *rows, chunk, ...]: the scan walks the chunks, each
    # chunk keeps the batch axis (and its split over a mesh) in front
    h_r = jnp.moveaxis(h.reshape(*rows, n // chunk, chunk, d), len(rows), 0)
    l_r = jnp.moveaxis(lbl.reshape(*rows, n // chunk, chunk), len(rows), 0)

    def body(carry, hl):
        acc, cnt = carry
        hc, lc = hl
        s, c = jax.checkpoint(chunk_loss)(hc, lc)
        return (acc + s, cnt + c), None

    (total, count), _ = lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (h_r, l_r))
    if reduction == "mean":
        return total / jnp.maximum(count, 1)
    return total


def fused_linear_cross_entropy(hidden, weight, label, chunk_size=1024,
                               transpose_weight=False, reduction="mean",
                               ignore_index=-100):
    """Chunked lm-head matmul + softmax cross-entropy that never
    materializes the full [tokens, vocab] logits (the memory-efficient CE;
    reference capability: fused_linear_param_grad_add + parallel
    cross-entropy tier, paddle/phi/kernels/fusion/). A lax.scan walks token
    chunks; each chunk's logits live only inside the chunk and are
    rematerialized in backward (jax.checkpoint), cutting peak HBM by
    ~2 x tokens x vocab x 4B at ~6% extra head FLOPs.

    hidden: [tokens, hidden] with label [tokens], or [batch, seq, hidden]
    with label [batch, seq]; weight: [hidden, vocab] (or [vocab, hidden]
    with transpose_weight=True, the tied-embedding layout).

    The 3-D form is what a model's training loss passes: a chunk is
    [batch, chunk_size // batch] tokens (the same chunk_size tokens of
    the whole batch), each row's sequence padded with ignore_index labels
    to a chunk multiple, so no chunk crosses a row. Under a data-parallel
    mesh a rank's rows then stay on it and each chip computes its own
    rows' loss; flattening batch into tokens first makes chunks that
    cross the ranks' boundary, and the partitioner gathers every rank's
    hidden states onto every chip on every chunk. Mean and sum are over
    the same tokens either way.
    """
    if reduction not in ("mean", "sum"):
        raise ValueError(
            f"fused_linear_cross_entropy supports reduction='mean'|'sum', "
            f"got {reduction!r} (use cross_entropy for per-token losses)")
    if len(hidden.shape) not in (2, 3) \
            or list(label.shape) != list(hidden.shape[:-1]):
        raise ValueError(
            f"fused_linear_cross_entropy takes hidden [tokens, hidden] or "
            f"[batch, seq, hidden] and label of its leading shape, got "
            f"{list(hidden.shape)} and {list(label.shape)}")
    return op_call("fused_linear_cross_entropy", _fused_linear_cross_entropy,
                   hidden, weight, label, chunk_size=chunk_size,
                   transpose_weight=bool(transpose_weight),
                   reduction=reduction, ignore_index=ignore_index)


@op_body("margin_cross_entropy")
def _margin_cross_entropy(lg, lbl, *, margin1, margin2, margin3, scale,
                          return_softmax, reduction):
    lbl = lbl.reshape(-1).astype(jnp.int32)
    onehot = jax.nn.one_hot(lbl, lg.shape[-1], dtype=lg.dtype)
    theta = jnp.arccos(jnp.clip(lg, -1.0 + 1e-7, 1.0 - 1e-7))
    target = jnp.cos(margin1 * theta + margin2) - margin3
    adjusted = jnp.where(onehot > 0, target, lg) * scale
    logp = jax.nn.log_softmax(adjusted.astype(jnp.float32), axis=-1)
    loss = -jnp.take_along_axis(logp, lbl[:, None], axis=-1)[:, 0]
    if reduction == "mean":
        loss = loss.mean()
    elif reduction == "sum":
        loss = loss.sum()
    if return_softmax:
        return loss, jax.nn.softmax(adjusted.astype(jnp.float32), -1)
    return loss


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5, margin3=0.0,
                         scale=64.0, group=None, return_softmax=False,
                         reduction="mean"):
    """Combined margin softmax (ArcFace family: cos(m1*t + m2) - m3;
    reference: ops.yaml margin_cross_entropy,
    margin_cross_entropy_kernel.cu). Expects cosine logits in [-1, 1]."""
    if group is not None:
        raise NotImplementedError(
            "margin_cross_entropy over a model-parallel group (class-dim "
            "sharded logits) is not implemented; use the local form or "
            "fleet ParallelCrossEntropy for the sharded softmax")
    return op_call("margin_cross_entropy", _margin_cross_entropy, logits,
                   label, margin1=margin1, margin2=margin2, margin3=margin3,
                   scale=scale, return_softmax=bool(return_softmax),
                   reduction=reduction)


@op_body("hsigmoid_loss")
def _hsigmoid_loss(x, lbl, w, *rest, num_classes, has_bias, has_path):
    i = 0
    b = None
    if has_bias:
        b = rest[i]
        i += 1
    if has_path:
        tbl = rest[i]
        code = rest[i + 1]
        mask = (tbl >= 0).astype(x.dtype)
        safe = jnp.maximum(tbl, 0).astype(jnp.int32)
    else:
        c = lbl.reshape(-1).astype(jnp.int32)
        n_leaf_base = num_classes - 1
        depth = max(1, int(math.ceil(math.log2(max(num_classes, 2)))))
        node = c + n_leaf_base          # heap leaf slot
        tbl_l, code_l, mask_l = [], [], []
        for _ in range(depth):
            parent = (node - 1) // 2
            is_right = (node == 2 * parent + 2)
            valid = node > 0
            tbl_l.append(jnp.where(valid, parent, 0))
            code_l.append(jnp.where(valid, is_right, False))
            mask_l.append(valid)
            node = jnp.where(valid, parent, 0)
        safe = jnp.stack(tbl_l, axis=1)             # [N, L] node ids
        code = jnp.stack(code_l, axis=1)
        mask = jnp.stack(mask_l, axis=1).astype(x.dtype)

    wp = w[safe]                                    # [N, L, D]
    z = jnp.einsum("nd,nld->nl", x, wp)
    if b is not None:
        z = z + b.reshape(-1)[safe]
    y = code.astype(x.dtype)
    # stable BCE-with-logits on (z, code)
    per_node = jnp.maximum(z, 0) - z * y + jnp.logaddexp(0.0, -jnp.abs(z))
    return (per_node * mask).sum(axis=1, keepdims=True)


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (reference: nn/functional/loss.py
    hsigmoid_loss, hierarchical_sigmoid kernels). Default tree: the
    complete binary tree over num_classes whose leaf for class c sits at
    heap slot c + num_classes - 1; the path to the root visits
    ceil(log2(C)) internal nodes, walked vectorized in-graph (static depth,
    data-dependent gathers — TPU-friendly). Custom trees come in as
    path_table/path_code [N, L] with negative entries masked.

    weight: [num_classes - 1, feature]; bias: [num_classes - 1].
    Returns [N, 1] per-sample losses (the reference's layout).
    """
    if is_sparse:
        raise NotImplementedError(
            "is_sparse=True selects the SelectedRows grad kernel in the "
            "reference; grads are dense here by design")
    args = [input, label, weight]
    if bias is not None:
        args.append(bias)
    if path_table is not None:
        if path_code is None:
            raise ValueError("path_table requires path_code")
        args += [path_table, path_code]
    return op_call("hsigmoid_loss", _hsigmoid_loss, *args,
                   num_classes=num_classes, has_bias=bias is not None,
                   has_path=path_table is not None)


@op_body("rnnt_loss")
def _rnnt_loss(logits, labels, in_len, lab_len, *, blank, fastemit_lambda,
               reduction):
    import jax.lax as lax

    b, t_max, u1, v = logits.shape
    u_max = u1 - 1
    lam = float(fastemit_lambda)
    neg_inf = jnp.asarray(-1e30, jnp.float32)

    def lattice_terms(logits):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        blank_lp = logp[..., blank]                        # [B,T,U+1]
        lab = labels.astype(jnp.int32)
        emit_lp = jnp.take_along_axis(
            logp[:, :, :u_max, :],
            lab[:, None, :, None].repeat(t_max, 1), -1)[..., 0]
        return blank_lp, emit_lp                            # [B,T,U]

    t_idx = in_len.astype(jnp.int32) - 1
    u_idx = lab_len.astype(jnp.int32)
    u_range = jnp.arange(u1)[None, :]

    def alpha_scan(blank_lp, emit_lp):
        def step(alpha_prev, t):
            from_blank = jnp.where(
                t == 0,
                jnp.where(u_range == 0, 0.0, neg_inf),
                alpha_prev + blank_lp[:, jnp.maximum(t - 1, 0), :])

            def emit_step(carry, u):
                cur = jnp.logaddexp(
                    from_blank[:, u], carry + emit_lp[:, t, u - 1])
                return cur, cur

            a0 = from_blank[:, 0]
            _, rest = lax.scan(emit_step, a0, jnp.arange(1, u1))
            alpha_t = jnp.concatenate(
                [a0[:, None], jnp.moveaxis(rest, 0, 1)], 1)
            return alpha_t, alpha_t

        alpha0 = jnp.full((b, u1), neg_inf)
        _, alphas = lax.scan(step, alpha0, jnp.arange(t_max))
        return jnp.moveaxis(alphas, 0, 1)                  # [B,T,U+1]

    def beta_scan(blank_lp, emit_lp):
        # beta(t,u): log-prob of completing from (t,u). Terminal:
        # beta(t_len-1, u_len) = blank there; outside the valid region -inf.
        valid_u = u_range <= u_idx[:, None]

        def step(beta_next, t):
            # t runs T-1 .. 0; beta_next = beta(t+1, :)
            at_term = (t == t_idx)
            blank_t = blank_lp[:, t, :]
            from_blank = jnp.where(
                at_term[:, None],
                jnp.where(u_range == u_idx[:, None], blank_t, neg_inf),
                beta_next + blank_t)

            def emit_step(carry, u):
                # carry = beta(t, u+1); emit (t,u) -> (t,u+1)
                cur = jnp.logaddexp(
                    from_blank[:, u],
                    carry + emit_lp[:, t, u])
                return cur, cur

            bU = from_blank[:, u1 - 1]
            _, rest = lax.scan(emit_step, bU,
                               jnp.arange(u1 - 2, -1, -1))
            beta_t = jnp.concatenate(
                [jnp.moveaxis(rest, 0, 1)[:, ::-1], bU[:, None]], 1)
            beta_t = jnp.where(valid_u, beta_t, neg_inf)
            return beta_t, beta_t

        beta0 = jnp.full((b, u1), neg_inf)
        _, betas = lax.scan(step, beta0,
                            jnp.arange(t_max - 1, -1, -1))
        return jnp.moveaxis(betas[::-1], 0, 1)             # [B,T,U+1]

    @jax.custom_vjp
    def nll_from_terms(blank_lp, emit_lp):
        alphas = alpha_scan(blank_lp, emit_lp)
        final = jnp.take_along_axis(jnp.take_along_axis(
            alphas, t_idx[:, None, None].repeat(u1, 2), 1)[:, 0, :],
            u_idx[:, None], 1)[:, 0]
        final_blank = jnp.take_along_axis(jnp.take_along_axis(
            blank_lp, t_idx[:, None, None].repeat(u1, 2), 1)[:, 0, :],
            u_idx[:, None], 1)[:, 0]
        return -(final + final_blank)

    def nll_fwd(blank_lp, emit_lp):
        alphas = alpha_scan(blank_lp, emit_lp)
        betas = beta_scan(blank_lp, emit_lp)
        nll = -betas[:, 0, 0]
        return nll, (alphas, betas, blank_lp, emit_lp, nll)

    def nll_bwd(res, ct):
        alphas, betas, blank_lp, emit_lp, nll = res
        logZ = -nll[:, None, None]
        t_r = jnp.arange(t_max)[None, :, None]
        u_r = jnp.arange(u1)[None, None, :]
        in_t = t_r < in_len.astype(jnp.int32)[:, None, None]
        # blank occupancy: alpha(t,u) + blank(t,u) + beta(t+1,u)
        beta_tp1 = jnp.concatenate(
            [betas[:, 1:, :], jnp.full((b, 1, u1), neg_inf)], 1)
        at_term = (t_r == t_idx[:, None, None]) & \
            (u_r == u_idx[:, None, None])
        blank_next = jnp.where(at_term, 0.0, beta_tp1)
        occ_blank = jnp.exp(jnp.clip(
            alphas + blank_lp + blank_next - logZ, -80, 0)) * in_t
        # emit occupancy: alpha(t,u) + emit(t,u) + beta(t,u+1)
        occ_emit = jnp.exp(jnp.clip(
            alphas[:, :, :u_max] + emit_lp + betas[:, :, 1:] - logZ,
            -80, 0)) * in_t
        # FastEmit: scale the emit-transition gradient by (1+lambda)
        occ_emit = occ_emit * (1.0 + lam)
        return (-occ_blank * ct[:, None, None],
                -occ_emit * ct[:, None, None])

    nll_from_terms.defvjp(nll_fwd, nll_bwd)

    blank_lp, emit_lp = lattice_terms(logits)
    nll = nll_from_terms(blank_lp, emit_lp)
    return _reduce_arr(nll, reduction)


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.001, reduction="mean", name=None):
    """RNN-Transducer loss (reference: nn/functional/loss.py:2054, CUDA
    warprnnt kernel phi/kernels/gpu/warprnnt_kernel.cu).

    input: [B, T, U+1, V] UNNORMALIZED logits (log-softmax applied here,
    as warprnnt does); label: [B, U] int; lengths per sample. Forward and
    backward lattice DPs run as lax.scans over T; gradients are the exact
    alpha/beta occupancies via a custom VJP, with FastEmit (Yu et al.
    2021) applied the way warp-transducer does: the EMIT-transition
    gradient at every lattice node is scaled by (1 + lambda) — the loss
    VALUE itself is the standard transducer NLL.
    """
    return op_call("rnnt_loss", _rnnt_loss, input, label, input_lengths,
                   label_lengths, blank=blank,
                   fastemit_lambda=fastemit_lambda, reduction=reduction)


@op_body("soft_margin_loss")
def _soft_margin_loss(z, y, *, reduction):
    # log(1 + exp(-y*z)) via softplus for stability
    return _reduce_arr(jax.nn.softplus(-y * z), reduction)


def soft_margin_loss(input, label, reduction="mean", name=None):
    """(reference: nn/functional/loss.py soft_margin_loss)."""
    return op_call("soft_margin_loss", _soft_margin_loss, input, label,
                   reduction=reduction)


@op_body("multi_label_soft_margin_loss")
def _multi_label_soft_margin_loss(z, y, *maybe_w, reduction):
    # -(y*log sigmoid(z) + (1-y)*log sigmoid(-z)) averaged over classes
    per = -(y * jax.nn.log_sigmoid(z) + (1 - y) * jax.nn.log_sigmoid(-z))
    if maybe_w:
        per = per * maybe_w[0]
    loss = per.mean(axis=-1)
    return _reduce_arr(loss, reduction)


def multi_label_soft_margin_loss(input, label, weight=None,
                                 reduction="mean", name=None):
    """(reference: loss.py multi_label_soft_margin_loss)."""
    args = [input, label] + ([weight] if weight is not None else [])
    return op_call("multi_label_soft_margin_loss",
                   _multi_label_soft_margin_loss, *args,
                   reduction=reduction)


@op_body("multi_margin_loss")
def _multi_margin_loss(z, y, *maybe_w, p, margin, reduction):
    n, c = z.shape
    y = y.astype(jnp.int32)
    gold = jnp.take_along_axis(z, y[:, None], axis=1)      # [n, 1]
    per_class = jnp.maximum(0.0, margin - gold + z) ** p   # [n, c]
    if maybe_w:
        per_class = per_class * maybe_w[0][y][:, None]
    # the gold class itself is excluded from the sum
    mask = jax.nn.one_hot(y, c, dtype=z.dtype)
    loss = ((1 - mask) * per_class).sum(axis=1) / c
    return _reduce_arr(loss, reduction)


def multi_margin_loss(input, label, p=1, margin=1.0, weight=None,
                      reduction="mean", name=None):
    """(reference: loss.py multi_margin_loss)."""
    args = [input, label] + ([weight] if weight is not None else [])
    return op_call("multi_margin_loss", _multi_margin_loss, *args,
                   p=p, margin=margin, reduction=reduction)


@op_body("gaussian_nll_loss")
def _gaussian_nll_loss(inp, lbl, var, *, full, epsilon, reduction):
    var = jnp.maximum(var, epsilon)
    loss = 0.5 * (jnp.log(var) + (inp - lbl) ** 2 / var)
    if full:
        loss = loss + 0.5 * jnp.log(jnp.asarray(2 * jnp.pi, inp.dtype))
    return _reduce_arr(loss, reduction)


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean", name=None):
    """(reference: loss.py gaussian_nll_loss). Negative variances raise
    eagerly (the reference's ValueError); under a trace the check cannot
    run."""
    import numpy as _np
    try:
        v = _np.asarray(variance.numpy() if hasattr(variance, "numpy")
                        else variance)
    except Exception:
        v = None
    if v is not None and v.size and v.min() < 0:
        raise ValueError("gaussian_nll_loss: var has negative entry/entries")
    return op_call("gaussian_nll_loss", _gaussian_nll_loss, input, label,
                   variance, full=bool(full), epsilon=epsilon,
                   reduction=reduction)


@op_body("poisson_nll_loss")
def _poisson_nll_loss(inp, lbl, *, log_input, full, epsilon, reduction):
    if log_input:
        loss = jnp.exp(inp) - lbl * inp
    else:
        # reference formula: log(input + epsilon), not a clamp
        loss = inp - lbl * jnp.log(inp + epsilon)
    if full:
        # Stirling approximation for label! (applied where label > 1)
        stirling = (lbl * jnp.log(jnp.maximum(lbl, 1.0)) - lbl
                    + 0.5 * jnp.log(2 * jnp.pi * jnp.maximum(lbl, 1.0)))
        loss = loss + jnp.where(lbl > 1, stirling, 0.0)
    return _reduce_arr(loss, reduction)


def poisson_nll_loss(input, label, log_input=True, full=False,
                     epsilon=1e-8, reduction="mean", name=None):
    """(reference: loss.py poisson_nll_loss)."""
    return op_call("poisson_nll_loss", _poisson_nll_loss, input, label,
                   log_input=bool(log_input), full=bool(full),
                   epsilon=epsilon, reduction=reduction)


@op_body("npair_loss")
def _npair_loss(anchor, positive, labels, *, l2_reg):
    """(reference: loss.py npair_loss; Sohn 2016): cross-entropy over
    anchor-positive similarity logits + L2 on the embeddings."""
    labels = labels.reshape(-1)
    batch = labels.shape[0]
    same = (labels[:, None] == labels[None, :]).astype(anchor.dtype)
    target = same / jnp.maximum(same.sum(axis=1, keepdims=True), 1.0)
    logits = anchor @ positive.T
    logp = jax.nn.log_softmax(logits, axis=1)
    ce = -(target * logp).sum(axis=1).mean()
    l2 = (jnp.sum(anchor ** 2) + jnp.sum(positive ** 2)) / batch
    return ce + l2_reg * l2 * 0.25


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """(reference: loss.py npair_loss)."""
    return op_call("npair_loss", _npair_loss, anchor, positive, labels,
                   l2_reg=l2_reg)


@op_body("adaptive_log_softmax_with_loss")
def _adaptive_log_softmax(h, lbl, head_w, *rest, cutoffs, has_head_bias,
                          n_tail):
    """Adaptive softmax (reference: loss.py adaptive_log_softmax_with_loss;
    Grave et al. 2017): frequent classes in the head, rare classes in
    down-projected tail clusters addressed via cluster logits. On TPU the
    per-cluster projections stay dense matmuls; cluster membership routes
    through masks (static shapes, no gather-by-partition)."""
    i = 0
    head_b = None
    if has_head_bias:
        head_b = rest[i]
        i += 1
    tails = rest[i:]
    head_logits = h @ head_w
    if head_b is not None:
        head_logits = head_logits + head_b
    head_logp = jax.nn.log_softmax(head_logits, axis=-1)
    n_head = head_w.shape[1] - n_tail
    out = jnp.zeros(h.shape[0], h.dtype)
    # head tokens: direct log-prob (negative labels are NOT head tokens —
    # same safe-index discipline as cross_entropy above)
    in_head = (lbl >= 0) & (lbl < cutoffs[0])
    safe_head = jnp.where(in_head, lbl, 0).astype(jnp.int32)
    lp_head = jnp.take_along_axis(head_logp, safe_head[:, None],
                                  axis=1)[:, 0]
    out = jnp.where(in_head, lp_head, out)
    # tail clusters: cluster logit + within-cluster log-prob
    for c in range(n_tail):
        lo = cutoffs[c]
        hi = cutoffs[c + 1]
        w1, w2 = tails[2 * c], tails[2 * c + 1]
        in_c = (lbl >= lo) & (lbl < hi)
        cluster_lp = head_logp[:, n_head + c]
        tail_logits = (h @ w1) @ w2
        tail_logp = jax.nn.log_softmax(tail_logits, axis=-1)
        safe = jnp.where(in_c, lbl - lo, 0).astype(jnp.int32)
        lp = jnp.take_along_axis(tail_logp, safe[:, None], axis=1)[:, 0]
        out = jnp.where(in_c, cluster_lp + lp, out)
    loss = -out.mean()
    return out, loss


def adaptive_log_softmax_with_loss(input, label, head_weight, tail_weights,
                                   cutoffs, head_bias=None, name=None):
    """(reference: loss.py adaptive_log_softmax_with_loss). Returns
    (per-token log-prob of the gold class, mean NLL loss). Labels must
    lie in [0, cutoffs[-1]); out-of-range labels raise eagerly (the
    reference's ValueError) — under a trace they cannot be checked and
    would poison the mean.

    head_weight: [hidden, n_head + n_clusters]; tail_weights: list of
    (proj [hidden, d_c], cls [d_c, cluster_size]) pairs; cutoffs:
    ascending class boundaries [c0, c1, ..., n_classes]."""
    import numpy as _np
    try:
        lab = _np.asarray(label.numpy() if hasattr(label, "numpy")
                          else label)
    except Exception:   # traced labels: the eager check cannot run
        lab = None
    if lab is not None and lab.size and (
            lab.min() < 0 or lab.max() >= int(cutoffs[-1])):
        raise ValueError(
            f"adaptive_log_softmax_with_loss: labels must be in "
            f"[0, {int(cutoffs[-1])}), got "
            f"[{int(lab.min())}, {int(lab.max())}]")
    args = [input, label, head_weight]
    if head_bias is not None:
        args.append(head_bias)
    for pair in tail_weights:
        args.extend(pair)
    return op_call("adaptive_log_softmax_with_loss", _adaptive_log_softmax,
                   *args, cutoffs=tuple(int(c) for c in cutoffs),
                   has_head_bias=head_bias is not None,
                   n_tail=len(tail_weights))


@op_body("dice_loss")
def _dice_loss(inp, label, *, epsilon):
    num_classes = inp.shape[-1]
    lab = jax.nn.one_hot(label.squeeze(-1).astype(jnp.int32), num_classes,
                         dtype=inp.dtype)
    rd = tuple(range(1, inp.ndim))
    inse = (inp * lab).sum(rd)
    denom = inp.sum(rd) + lab.sum(rd)
    return (1 - 2 * inse / (denom + epsilon)).mean()


def dice_loss(input, label, epsilon=1e-5, name=None):
    """(reference: python/paddle/nn/functional/loss.py dice_loss): label
    holds class ids with trailing singleton dim; scalar mean dice."""
    return op_call("dice_loss", _dice_loss, input, label, epsilon=epsilon)


@op_body("log_loss")
def _log_loss(inp, label, *, epsilon):
    return (-label * jnp.log(inp + epsilon)
            - (1 - label) * jnp.log(1 - inp + epsilon))


def log_loss(input, label, epsilon=1e-4, name=None):
    """(reference: loss.py log_loss): elementwise negative log likelihood
    of binary probabilities."""
    return op_call("log_loss", _log_loss, input, label, epsilon=epsilon)


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None, margin=1.0,
                                      swap=False, reduction="mean",
                                      name=None):
    """(reference: loss.py triplet_margin_with_distance_loss): like
    triplet_margin_loss but with a caller-supplied distance callable."""
    if reduction not in ("mean", "sum", "none"):
        raise ValueError("reduction must be 'mean', 'sum' or 'none'")
    from ... import tensor as T

    def _l2(a, b):
        return T.sqrt(((a - b) ** 2).sum(-1) + 1e-12)

    dist = distance_function or _l2
    d_pos = dist(input, positive)
    d_neg = dist(input, negative)
    if swap:
        d_pn = dist(positive, negative)
        d_neg = T.minimum(d_neg, d_pn)
    loss = T.clip(d_pos - d_neg + margin, min=0.0)
    if reduction == "mean":
        return loss.mean()
    if reduction == "sum":
        return loss.sum()
    return loss
