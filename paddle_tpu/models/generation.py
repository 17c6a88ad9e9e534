"""Incremental decoding: KV-cache generation loop shared by the model
families.

Reference capability: the decode path the reference serves through
fusion/gpu/masked_multihead_attention.cu + PaddleNLP's generate().
TPU-native design: fixed-size caches + a scalar offset tensor keep every
decode step the SAME static-shape program — XLA compiles it once and each
subsequent token reuses the executable (the analog of the reference's
persistent decode kernel).  Prefill writes the prompt's K/V in one pass.
"""
from __future__ import annotations

from ..core.state import no_grad
from ..tensor_ops import creation
from ..tensor_ops import manipulation as MA


def init_kv_caches(num_layers, batch, max_len, num_heads, head_dim,
                   dtype="float32", per_row_offsets=False):
    """Per-layer {'k','v','offset'} cache dicts ([B, max_len, H, D]).

    ``per_row_offsets=True`` makes the offset an int32 [B] vector (one
    clock per row — the serving-slot/speculative-decoding shape, where
    rows advance unevenly) instead of the shared scalar."""
    caches = []
    offset = creation.zeros([batch] if per_row_offsets else [],
                            dtype="int32")
    for _ in range(num_layers):
        caches.append({
            "k": creation.zeros([batch, max_len, num_heads, head_dim],
                                dtype=dtype),
            "v": creation.zeros([batch, max_len, num_heads, head_dim],
                                dtype=dtype),
            "offset": offset,
        })
    return caches


def recurrent_layer_states(cfg, dtype="float32"):
    """What ``cfg.layer_states(dtype)`` says each layer keeps per
    sequence (None: keys and values; else ``{name: (shape, dtype[, the
    shape a row is read back in])}`` of a fixed-size recurrent state),
    or None when the config has no such
    method or every layer keeps keys and values."""
    states = getattr(cfg, "layer_states", None)
    states = None if states is None else states(dtype)
    return states if states and any(s is not None for s in states) \
        else None


def _advance(caches, n):
    off = caches[0]["offset"] + n
    for c in caches:
        c["offset"] = off


def _seen_mask(ids, vocab):
    """[B, S] ids → [B, V] bool mask of tokens that have appeared."""
    from ..nn import functional as F
    return F.one_hot(ids, num_classes=vocab).sum(axis=1) > 0


def apply_logit_processors(logits_last, temperature=1.0, top_k=None,
                           top_p=None, repetition_penalty=None, seen=None):
    """[B, V] → [B, V] processed logits, HF order: repetition penalty
    (also for greedy) → temperature → top-k → top-p (nucleus).  `seen`
    is the fixed-shape [B, V] already-emitted mask (so every decode step
    stays the same static-shape program).  top_k >= vocab is a no-op
    (clamped), top_p=1.0 is a no-op.  Shared by generate() and the
    serving engine's per-slot sampling."""
    from ..tensor_ops import search as S
    from ..nn import functional as F
    if repetition_penalty is not None and repetition_penalty != 1.0 \
            and seen is not None:
        pos = logits_last > 0
        penalized = S.where(pos, logits_last / repetition_penalty,
                            logits_last * repetition_penalty)
        logits_last = S.where(seen, penalized, logits_last)
    if temperature == 0.0:
        return logits_last          # greedy: argmax is scale-invariant
    logits_last = logits_last / temperature
    if top_k is not None:
        k = min(int(top_k), logits_last.shape[-1])
        vals, _ = S.topk(logits_last, k)
        minv = vals[:, -1:]
        logits_last = MA.masked_fill(logits_last, logits_last < minv,
                                     float("-inf"))
    if top_p is not None and top_p < 1.0:
        vocab = logits_last.shape[-1]
        sorted_logits, _ = S.topk(logits_last, vocab)   # desc full sort
        probs = F.softmax(sorted_logits, axis=-1)
        cum = probs.cumsum(axis=-1)
        # keep the smallest prefix whose mass reaches top_p (the first
        # token always survives: its EXCLUSIVE prefix mass is 0)
        keep = (cum - probs) < top_p
        minv = MA.masked_fill(sorted_logits, ~keep,
                              float("inf")).min(axis=-1, keepdim=True)
        logits_last = MA.masked_fill(logits_last, logits_last < minv,
                                     float("-inf"))
    return logits_last


def sample_next_token(logits_last, temperature=0.0, top_k=None, top_p=None,
                      repetition_penalty=None, seen=None):
    """[B, V] → [B] next tokens: apply_logit_processors then argmax
    (temperature=0) or multinomial sampling."""
    from ..tensor_ops import random as R, search as S
    from ..nn import functional as F
    logits_last = apply_logit_processors(
        logits_last, temperature=temperature, top_k=top_k, top_p=top_p,
        repetition_penalty=repetition_penalty, seen=seen)
    if temperature == 0.0:
        return S.argmax(logits_last, axis=-1)
    probs = F.softmax(logits_last, axis=-1)
    return MA.reshape(R.multinomial(probs, 1), [-1])


_sample = sample_next_token


class _EosTracker:
    """Per-sequence finished flags accumulated ACROSS steps: sequence i is
    done once it has emitted eos at ANY step, not only when the whole
    batch emits it simultaneously."""

    def __init__(self, batch, eos_token_id):
        import numpy as np
        self.eos = eos_token_id
        self.done = np.zeros(batch, bool) if eos_token_id is not None \
            else None

    def update(self, nxt):
        if self.done is None:
            return False
        import numpy as np
        self.done |= np.asarray(nxt._data_) == self.eos
        return bool(self.done.all())

    def force(self, nxt):
        """Rows already finished BEFORE this step keep emitting eos —
        not live samples — so an unevenly-finishing batch never grows
        garbage suffixes past each row's eos."""
        if self.done is None or not self.done.any():
            return nxt
        import numpy as np
        from ..core.tensor import Tensor
        arr = np.array(np.asarray(nxt._data_))
        arr[self.done] = self.eos
        return Tensor(arr)


def generate(model, input_ids, max_new_tokens=32, temperature=0.0,
             top_k=None, top_p=None, repetition_penalty=None,
             use_cache=True, eos_token_id=None):
    """Autoregressive decoding.  Returns [B, S + n_generated] token ids.

    use_cache=True runs the masked-MHA KV-cache path (every step is one
    fixed-shape compiled program); use_cache=False re-runs the full
    forward per token (the O(S²)-per-step fallback, kept for parity
    checks).  With eos_token_id, decoding stops early once EVERY
    sequence in the batch has emitted it."""
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if repetition_penalty is not None and repetition_penalty <= 0.0:
        raise ValueError(
            f"repetition_penalty must be > 0, got {repetition_penalty}")
    cfg = model.config
    b, s = input_ids.shape
    max_len = min(cfg.max_seq_len, s + max_new_tokens)
    n_new = max_len - s
    if n_new <= 0:
        return input_ids

    with no_grad():
        if not use_cache:
            tracker = _EosTracker(b, eos_token_id)
            ids = input_ids
            use_pen = repetition_penalty is not None and \
                repetition_penalty != 1.0
            seen = _seen_mask(ids, cfg.vocab_size) if use_pen else None
            for _ in range(n_new):
                logits = model(ids)
                nxt = _sample(logits[:, -1, :], temperature, top_k,
                              top_p, repetition_penalty, seen=seen)
                nxt = tracker.force(nxt)
                if use_pen:
                    seen = seen | _seen_mask(MA.reshape(nxt, [b, 1]),
                                             cfg.vocab_size)
                ids = MA.concat([ids, MA.reshape(nxt, [b, 1])], axis=1)
                if tracker.update(nxt):
                    break
            return ids

        windows = getattr(cfg, "layer_windows", None)
        if windows is not None and any(w is not None for w in windows()):
            raise NotImplementedError(
                f"generate(use_cache=True) for {type(model).__name__}: "
                "its sliding_attention layers see a window of positions, "
                "which the dense caches built here do not have; serve it "
                "through serving.Engine (page tables by layer kind) or "
                "pass use_cache=False")
        latents = getattr(cfg, "layer_latents", None)
        if latents is not None and any(w is not None for w in latents()):
            raise NotImplementedError(
                f"generate(use_cache=True) for {type(model).__name__}: "
                "its layers cache one latent row a token, which the dense "
                "key/value caches built here do not hold; serve it "
                "through serving.Engine (latent pages) or pass "
                "use_cache=False")
        if hasattr(model, "init_caches"):
            # a model whose layers do not all keep keys and values
            # builds its own per-layer caches
            caches = model.init_caches(b, max_len)
        else:
            # GQA caches hold num_kv_heads rows; MMHA groups Q heads
            # natively
            kv_heads = getattr(cfg, "num_kv_heads", cfg.num_heads)
            caches = init_kv_caches(
                cfg.num_layers, b, max_len, kv_heads, cfg.head_dim,
                dtype="float32")
        tracker = _EosTracker(b, eos_token_id)
        logits = model(input_ids, caches=caches)      # prefill
        _advance(caches, s)
        pieces = [input_ids]
        use_pen = repetition_penalty is not None and \
            repetition_penalty != 1.0
        # fixed-shape [B, V] mask updated per token: the decode step
        # stays the same static program regardless of prefix length
        seen = _seen_mask(input_ids, cfg.vocab_size) if use_pen else None
        nxt = _sample(logits[:, -1, :], temperature, top_k, top_p,
                      repetition_penalty, seen=seen)
        for _ in range(n_new - 1):
            tok = MA.reshape(nxt, [b, 1])
            pieces.append(tok)
            if tracker.update(nxt):
                return MA.concat(pieces, axis=1)
            if use_pen:
                seen = seen | _seen_mask(tok, cfg.vocab_size)
            logits = model(tok, caches=caches)
            _advance(caches, 1)
            nxt = _sample(logits[:, -1, :], temperature, top_k, top_p,
                          repetition_penalty, seen=seen)
            nxt = tracker.force(nxt)
        pieces.append(MA.reshape(nxt, [b, 1]))
        return MA.concat(pieces, axis=1)


def speculative_generate(model, draft_model, input_ids,
                         max_new_tokens=32, speculation_k=4,
                         eos_token_id=None):
    """Greedy draft-model speculative decoding (Leviathan et al.):
    the small `draft_model` proposes K tokens per window, `model`
    verifies all K+1 positions in ONE batched call, and the leading
    run of proposals matching the target's argmaxes is accepted plus
    the bonus token after it.  Every emitted token is a target-model
    greedy argmax, so outputs match `generate(..., temperature=0.0)`;
    the draft only decides how many tokens each window yields.

    Both models keep dense KV caches with per-row int32 offset vectors
    (rows accept different amounts, so each row has its own clock); a
    rejected tail needs no cache surgery — rewinding the offset masks
    it causally and the next window overwrites it.  K/V capacity
    carries `speculation_k` positions of headroom for the verify
    window's overshoot; positions past the accept boundary are never
    attended by an accepted prediction, so the overshoot is inert.

    `speculation_k=0` is exactly `generate` (greedy).  Returns
    [B, S + n] ids; with `eos_token_id`, finished rows pad with eos
    like `generate` and decoding stops when every row finished."""
    import numpy as np
    from ..core.tensor import Tensor
    from ..tensor_ops import search as S

    K = int(speculation_k)
    if K <= 0:
        return generate(model, input_ids, max_new_tokens=max_new_tokens,
                        temperature=0.0, eos_token_id=eos_token_id)
    cfg = model.config
    dcfg = draft_model.config
    for which, c in (("model", cfg), ("draft_model", dcfg)):
        if recurrent_layer_states(c) is not None:
            raise NotImplementedError(
                f"speculative_generate: {which} has layers that keep a "
                "recurrent state, and a rejected tail cannot be rewound "
                "out of a recurrence by moving an offset")
    b, s = input_ids.shape
    max_len = min(cfg.max_seq_len, s + max_new_tokens)
    n_new = max_len - s
    if n_new <= 0:
        return input_ids
    if dcfg.vocab_size != cfg.vocab_size:
        raise ValueError(f"draft vocab {dcfg.vocab_size} != target "
                         f"vocab {cfg.vocab_size}")
    cap = max_len + K
    kv_t = getattr(cfg, "num_kv_heads", cfg.num_heads)
    kv_d = getattr(dcfg, "num_kv_heads", dcfg.num_heads)

    def _argmax_np(logits):
        return np.asarray(S.argmax(logits, axis=-1)._data_)

    with no_grad():
        caches = init_kv_caches(cfg.num_layers, b, cap, kv_t,
                                cfg.head_dim, per_row_offsets=True)
        d_caches = init_kv_caches(dcfg.num_layers, b, cap, kv_d,
                                  dcfg.head_dim, per_row_offsets=True)

        def set_offsets(cs, off_np):
            off_t = Tensor(np.asarray(off_np, np.int32))
            for c in cs:
                c["offset"] = off_t

        ids_np = np.asarray(input_ids._data_, np.int32)
        logits = model(input_ids, caches=caches)          # prefill
        draft_model(input_ids, caches=d_caches)
        off = np.full(b, s, np.int32)          # target rows' clocks
        d_off = np.full(b, s, np.int32)        # draft rows' clocks
        set_offsets(caches, off)
        set_offsets(d_caches, d_off)
        first = _argmax_np(logits[:, -1, :])
        rows = [[int(first[r])] for r in range(b)]
        last = first.astype(np.int32)
        done = np.zeros(b, bool)
        if eos_token_id is not None:
            done |= first == eos_token_id

        def known(r, pos):
            return int(ids_np[r, pos]) if pos < s \
                else rows[r][pos - s]

        while not done.all() and any(len(t) < n_new for t in rows):
            # --- draft K proposer steps (teacher-forced catch-up) ---
            prev = last.copy()
            d_out = [[] for _ in range(b)]
            d_start = d_off.copy()
            for j in range(K):
                tok_in = np.zeros((b, 1), np.int32)
                for r in range(b):
                    p = int(d_start[r]) + j
                    tok_in[r, 0] = known(r, p) if p <= off[r] \
                        else prev[r]
                set_offsets(d_caches, d_start + j)
                dl = draft_model(Tensor(tok_in), caches=d_caches)
                step = _argmax_np(dl[:, -1, :])
                for r in range(b):
                    prev[r] = int(step[r])
                    d_out[r].append(int(step[r]))
            # --- one batched verify of [last, d_1..d_K] ---
            tok_in = np.zeros((b, K + 1), np.int32)
            caps_row = np.zeros(b, np.int32)
            for r in range(b):
                lag = int(off[r] - d_start[r])
                caps_row[r] = max(0, K - lag)
                tok_in[r, 0] = last[r]
                for i in range(1, K + 1):
                    tok_in[r, i] = d_out[r][lag + i - 1] \
                        if i <= caps_row[r] else last[r]
            set_offsets(caches, off)
            t = _argmax_np(model(Tensor(tok_in), caches=caches))
            # --- accept runs + per-row offset rewind ---
            for r in range(b):
                if done[r]:
                    continue
                a = 0
                while a < caps_row[r] and tok_in[r, a + 1] == t[r, a]:
                    a += 1
                for i in range(a + 1):
                    if len(rows[r]) >= n_new or done[r]:
                        break
                    tok = int(t[r, i])
                    rows[r].append(tok)
                    last[r] = tok
                    off[r] += 1
                    d_off[r] = min(d_start[r] + K, off[r])
                    if eos_token_id is not None and \
                            tok == eos_token_id:
                        done[r] = True
            done |= np.array([len(t) >= n_new for t in rows])

    width = max(len(t) for t in rows)
    pad = eos_token_id if eos_token_id is not None else 0
    out = np.full((b, width), pad, ids_np.dtype)
    for r, toks in enumerate(rows):
        out[r, :len(toks)] = toks
        if eos_token_id is None and len(toks) < width:
            out[r, len(toks):] = toks[-1]      # unreachable: no-eos
    return MA.concat([input_ids, Tensor(out)], axis=1)


def beam_search(model, input_ids, max_new_tokens=32, num_beams=4,
                eos_token_id=None, length_penalty=1.0):
    """Beam-search decoding over the full-forward path (correctness
    first; the sampling paths own the fixed-shape KV-cache fast lane).

    Standard log-prob beams: expand each batch row to `num_beams`
    hypotheses, score token extensions with cumulative log-probs, keep
    the top beams per row each step, and return the best finished (or
    longest) hypothesis per row, length-normalized by
    `len**length_penalty`.  Returns [B, S + n] ids."""
    import numpy as np
    from ..core.tensor import Tensor
    from ..nn import functional as F

    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    b, s = input_ids.shape
    cfg = model.config
    n_new = min(cfg.max_seq_len, s + max_new_tokens) - s
    if n_new <= 0:
        return input_ids
    k = int(num_beams)

    ids = np.asarray(input_ids._data_)
    beams = np.repeat(ids, k, axis=0)                  # [B*K, S]
    scores = np.full((b, k), -np.inf, np.float64)
    scores[:, 0] = 0.0                                 # first beam only
    done = np.zeros((b, k), bool)
    lens = np.zeros((b, k), np.int64)   # per-hypothesis generated length

    with no_grad():
        for _ in range(n_new):
            logits = model(Tensor(beams))
            logp = np.asarray(F.log_softmax(
                logits[:, -1, :], axis=-1)._data_, np.float64)
            vocab = logp.shape[-1]
            logp = logp.reshape(b, k, vocab)
            # finished beams only extend with a frozen score
            cand = scores[:, :, None] + np.where(done[:, :, None],
                                                 -np.inf, logp)
            if eos_token_id is not None:
                # a finished beam keeps exactly one continuation (pad
                # with eos at frozen score) so it stays selectable
                cand[:, :, eos_token_id] = np.where(
                    done, scores, cand[:, :, eos_token_id])
            flat = cand.reshape(b, k * vocab)
            top = np.argsort(-flat, axis=1)[:, :k]     # [B, K]
            new_scores = np.take_along_axis(flat, top, axis=1)
            src_beam = top // vocab
            tok = (top % vocab).astype(beams.dtype)

            picked = beams.reshape(b, k, -1)[np.arange(b)[:, None],
                                             src_beam]
            beams = np.concatenate([picked, tok[:, :, None]],
                                   axis=2).reshape(b * k, -1)
            done = np.take_along_axis(done, src_beam, axis=1)
            lens = np.take_along_axis(lens, src_beam, axis=1)
            lens = lens + (~done)       # finished beams stop growing
            if eos_token_id is not None:
                done = done | (tok == eos_token_id)
            scores = new_scores
            if done.all():
                break

    # pick the best beam per row, normalized by each HYPOTHESIS's own
    # generated length (early-finished beams are shorter)
    norm = scores / np.maximum(lens, 1) ** length_penalty
    best = norm.argmax(axis=1)
    out = beams.reshape(b, k, -1)[np.arange(b), best]
    return Tensor(out)
