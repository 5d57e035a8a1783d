"""Plain reference: a decoder of grouped-query attention and routed
SwiGLU experts that GENERATES BY DIFFUSION OVER BLOCKS (SDAR,
``model_type: sdar_moe``; the layer is Qwen3-MoE's), as one chip of an
expert-parallel deployment computes it.

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``;
no kernel, no cache, no batching; it imports nothing of the program.
One sequence at a time runs through every layer, the layer loop
outermost so that one float32 layer is resident, the experts one at a
time, attention one block of queries at a time.

The layer (RMSNorm ``rms_norm_eps``, no biases), ``B`` the block
length, ``n`` positions:

* ``h = norm(x; w_attn)``; ``q = h W_q`` as ``num_attention_heads``
  heads of ``head_dim``, ``k = h W_k`` and ``v = h W_v`` as
  ``num_key_value_heads``.  Each head of ``q`` and of ``k`` is
  RMS-normalised over its ``head_dim`` (``q_norm``, ``k_norm``), then
  rotated: RoPE, rotate-half (dimension ``i`` pairs with ``i +
  head_dim / 2``), ``theta = rope_theta``, at the absolute position.
* ``scores(i, j) = q_i . k_j / sqrt(head_dim)`` where ``j // B <= i //
  B`` (BLOCK-CAUSAL), minus infinity elsewhere; query head ``h`` reads
  kv head ``h // (heads / kv heads)``; ``x += softmax(scores) v W_o``.
* ``h2 = norm(x; w_ffn)``; ``p = softmax(h2 W_r)`` over ALL experts;
  the ``num_experts_per_tok`` largest kept, ``g = p_top / sum p_top``;
  ``x += sum_e g_e W_down_e (silu(W_gate_e h2) * W_up_e h2)`` over the
  chosen experts THAT ARE HELD HERE (the configuration's share; the
  other chips' experts add their part elsewhere, and nothing stands in
  for them).
* ``logits = norm(x; w_final) W_head``.  NO SHIFT: row ``p`` is the
  distribution of the token AT position ``p``.

Generation, replayed teacher-forced (:func:`run`).  Positions are
blocks of ``B`` by absolute index.  A generated block starts with its
ungiven positions ``[MASK]`` (the prompt's last ``L mod B`` tokens open
the first as given).  A denoise pass forwards the sequence so far with
the block as it stands (``[MASK]``'s embedding where masked), takes at
each masked position the largest probability as its confidence,
and commits by the rule: *static*, ``T`` passes a block, pass ``s``
commits the ``B // T`` (+1 for ``s < B mod T``) masked positions of
largest confidence (ties to the lower position), never more than
remain; *dynamic*, every masked position above the threshold when those
are at least the static count, else the static choice.  The SERVED
tokens are committed at the chosen positions, and the logits row of
that pass at that position is what the replay returns for it.
Positions of the answer's last block past the served length stay
masked and are never committed (the configuration's ``assumed``).

Departures.  (1) Every pass ``s`` of EVERY block of a sequence is one
forward of ``[the sequence's tokens | its generated blocks as pass s
sees them]``: a row of the second part sees the first part's rows of
EARLIER blocks and the second part's rows of its OWN block.  Under the
block-causal mask no position sees a later block, so that is, block by
block, the forward of the sequence so far with the block as it stands
(``run(..., sequential=True)`` does it that way, one forward a block a
pass; a test holds the two together).  (2) Row counts are padded to a
multiple of ``PAD`` (of ``ROWS``, under ``PAD``) so that few shapes
compile; a padding row sees itself alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 1024         # row padding quantum
ROWS = 256         # queries attended at a time
HEAD_ROWS = 128    # rows the output head takes at a time
HIGHEST = "highest"
CLEAN, NOISY, PADDING = 0, 1, 2


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight


def rotate_half(x, angles):
    """``x (..., hd)`` turned by ``angles (..., hd / 2)``: dimension
    ``i`` pairs with ``i + hd / 2``."""
    half = x.shape[-1] // 2
    first, second = x[..., :half], x[..., half:]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def visible(q_stream, q_block, k_stream, k_block, q_row, k_row):
    """Which keys a query row sees (see the module's Departures)."""
    clean = (q_stream == CLEAN) & (k_stream == CLEAN) & (k_block <= q_block)
    noisy = (q_stream == NOISY) & (
        ((k_stream == CLEAN) & (k_block < q_block))
        | ((k_stream == NOISY) & (k_block == q_block)))
    return clean | noisy | (q_row == k_row)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads",
                                             "head_dim", "eps", "theta"))
def attention(layer, x, position, stream, block, *, heads, kv_heads,
              head_dim, eps, theta):
    rows = x.shape[0]
    u = rms_norm(x, layer["attn_norm"], eps)
    frequencies = theta ** (-jnp.arange(0, head_dim, 2,
                                        dtype=jnp.float32) / head_dim)
    angles = position[:, None].astype(jnp.float32) * frequencies
    q = (u @ layer["wq"]).reshape(rows, heads, head_dim)
    k = (u @ layer["wk"]).reshape(rows, kv_heads, head_dim)
    v = (u @ layer["wv"]).reshape(rows, kv_heads, head_dim)
    q = rotate_half(rms_norm(q, layer["q_norm"], eps), angles[:, None])
    k = rotate_half(rms_norm(k, layer["k_norm"], eps), angles[:, None])
    group = heads // kv_heads
    q = q.reshape(rows, kv_heads, group, head_dim)
    row = jnp.arange(rows)

    def some(start):
        take = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=start, slice_size=ROWS)
        scores = jnp.einsum("qkgd,skd->kgqs", take(q), k) \
            * head_dim ** -0.5
        seen = visible(take(stream)[:, None], take(block)[:, None],
                       stream[None, :], block[None, :],
                       take(row)[:, None], row[None, :])
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        out = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, -1), v)
        return out.reshape(ROWS, heads * head_dim)

    out = jax.lax.map(some, jnp.arange(0, rows, ROWS)).reshape(rows, -1)
    return x + out @ layer["wo"]


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def gates(layer, x, *, top_k, eps):
    """(normed x, (rows, E) gates over ALL experts, zero off the chosen
    ones)."""
    u = rms_norm(x, layer["ffn_norm"], eps)
    probs = jax.nn.softmax(u @ layer["router"], -1)
    chosen, ids = jax.lax.top_k(probs, top_k)
    chosen = chosen / chosen.sum(-1, keepdims=True)
    dense = jnp.zeros_like(probs)
    dense = dense.at[jnp.arange(x.shape[0])[:, None], ids].set(chosen)
    return u, dense


@jax.jit
def expert_term(expert, u, gate):
    hidden = jax.nn.silu(u @ expert["w_gate"]) * (u @ expert["w_up"])
    return (hidden @ expert["w_down"]) * gate[:, None]


def experts(cfg, weights, index, layer, states, held):
    """The feed-forward block over every sequence of ``states``, with
    the routed experts ``held`` (a range of expert numbers) evaluated
    one at a time: one expert's float32 weights are resident, and every
    sequence rides through them."""
    routes = [gates(layer, x, top_k=cfg["num_experts_per_tok"],
                    eps=cfg["rms_norm_eps"]) for x in states]
    totals = list(states)
    for which in held:
        expert = weights.expert(index, which)
        totals = [total + expert_term(expert, u, dense[:, which])
                  for total, (u, dense) in zip(totals, routes)]
    return totals


@functools.partial(jax.jit, static_argnames=("eps",))
def head(top, rows, *, eps):
    return rms_norm(rows, top["final_norm"], eps) @ top["lm_head"]


@functools.partial(jax.jit, static_argnames=("eps",))
def confidence(top, rows, *, eps):
    """The largest probability of each of ``HEAD_ROWS`` rows'
    distributions."""
    logits = head(top, rows, eps=eps)
    return jnp.exp(logits.max(-1) - jax.nn.logsumexp(logits, -1))


def held_experts(cfg):
    lowest = cfg.get("experts_first", 0)
    return range(lowest, lowest + cfg["num_experts"])


def hidden_states(cfg, weights, top, sequences):
    """Final hidden rows of every sequence of ``sequences``: each is
    ``(ids, masked, position, stream, block)`` row arrays of one
    length (a multiple of ``ROWS``)."""
    mask_row = top["embed"][cfg["assumed"]["mask_token_id"]]
    states = [jnp.where(jnp.asarray(masked)[:, None], mask_row,
                        top["embed"][jnp.asarray(ids)])
              for ids, masked, *_ in sequences]
    places = [tuple(jnp.asarray(a, jnp.int32) for a in rest)
              for _, _, *rest in sequences]
    for index in range(cfg["num_hidden_layers"]):
        layer = weights.layer(index)
        states = [attention(
            layer, x, *place, heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]))
            for x, place in zip(states, places)]
        states = experts(cfg, weights, index, layer, states,
                         held_experts(cfg))
        del layer
    return states


def _padded(arrays, fill):
    """Row arrays padded to a multiple of ``PAD`` (of ``ROWS``, under
    ``PAD``); ``fill`` a value each."""
    rows = len(arrays[0])
    quantum = PAD if rows > PAD else ROWS
    total = -(-rows // quantum) * quantum
    return tuple(np.concatenate(
        [np.asarray(a), np.full(total - rows, value, np.asarray(a).dtype)])
        for a, value in zip(arrays, fill))


def forward(cfg, weights, tokens, masked=None):
    """Logits ``(n, vocab)`` of ONE full forward of ``tokens`` under
    the block-causal mask, ``masked`` positions carrying ``[MASK]``'s
    embedding; row ``p`` is the distribution of the token AT ``p``."""
    B = cfg["serving"]["block_length"]
    n = len(tokens)
    masked = np.zeros(n, bool) if masked is None else np.asarray(masked)
    position = np.arange(n)
    rows = _padded((np.asarray(tokens, np.int32), masked, position,
                    np.full(n, CLEAN), position // B),
                   (0, False, 0, PADDING, 0))
    with jax.default_matmul_precision(HIGHEST):
        top = weights.top()
        x, = hidden_states(cfg, weights, top, [rows])
        return _logits(top, x, np.arange(n), cfg["rms_norm_eps"])


def _head_rows(x, rows):
    """The rows ``rows`` of ``x``, their count rounded up to whole
    ``HEAD_ROWS`` (with copies of row 0) so that a count is not a shape
    of its own."""
    take = np.zeros(-(-len(rows) // HEAD_ROWS) * HEAD_ROWS, np.int32)
    take[:len(rows)] = rows
    return x[jnp.asarray(take)]


def _over_head_rows(function, top, x, rows, eps):
    """``function(top, HEAD_ROWS rows, eps=)`` over the rows ``rows`` of
    ``x``, a chunk at a time, as one numpy array."""
    taken = _head_rows(x, rows)
    out = [np.asarray(function(top, taken[at:at + HEAD_ROWS], eps=eps))
           for at in range(0, len(taken), HEAD_ROWS)]
    return np.concatenate(out)[:len(rows)]


def _logits(top, x, rows, eps):
    """The head over the rows ``rows`` of ``x``."""
    if not len(rows):
        return np.zeros((0, top["lm_head"].shape[1]), np.float32)
    return _over_head_rows(head, top, x, rows, eps)


def commit(conf, masked, pass_index, cfg):
    """The rule on ONE block: ``conf`` and ``masked`` over its ``B``
    positions (``masked``: those that may still be committed) ->
    the positions this pass commits."""
    serving = cfg["serving"]
    B, T = serving["block_length"], serving["denoise_steps"]
    candidates = [p for p in range(B) if masked[p]]
    count = B // T + (1 if pass_index < B % T else 0)
    if pass_index >= T:
        count = B
    if serving["denoise_rule"] == "dynamic":
        high = [p for p in candidates
                if conf[p] > serving["denoise_threshold"]]
        if len(high) >= count:
            return high
    ranked = sorted(candidates, key=lambda p: (-conf[p], p))
    return sorted(ranked[:count])


class _Replay:
    """One sequence's generated blocks while they are replayed."""

    def __init__(self, cfg, tokens, span):
        self.B = B = cfg["serving"]["block_length"]
        self.tokens = np.asarray(tokens, np.int32)
        self.prompt = prompt = span[0] + 1       # see run()
        self.n = n = span[1] + 1
        assert n == len(tokens)
        self.first = prompt // B * B             # first generated block
        self.length = -(-n // B) * B             # whole blocks
        #: Per position from ``first``: still masked (the tail past
        #: ``n`` stays so), and may still be committed.
        position = np.arange(self.first, self.length)
        self.masked = position >= prompt
        self.open = self.masked & (position < n)
        self.passes = np.zeros(len(position) // B, np.int32)
        self.rows = {}                           # position -> logits row

    def whole(self):
        out = np.zeros(self.length, np.int32)
        out[:self.n] = self.tokens
        return out

    def unfinished(self):
        return [b for b in range(len(self.passes))
                if self.open[b * self.B:(b + 1) * self.B].any()]

    def layout(self):
        """Rows of one forward: the sequence, then its generated
        blocks as they stand."""
        B, whole = self.B, self.whole()
        clean = np.arange(self.length)
        noisy = np.arange(self.first, self.length)
        return _padded(
            (np.concatenate([whole, whole[self.first:]]),
             np.concatenate([np.zeros(self.length, bool), self.masked]),
             np.concatenate([clean, noisy]),
             np.concatenate([np.full(self.length, CLEAN),
                             np.full(len(noisy), NOISY)]),
             np.concatenate([clean, noisy]) // B),
            (0, False, 0, PADDING, 0))

    def apply(self, cfg, conf, logits_of, blocks):
        """One pass's confidences (per position from ``first``) ->
        commits of ``blocks``; ``logits_of(positions)`` gives their
        rows of this pass."""
        B, chosen = self.B, []
        for b in blocks:
            at = slice(b * B, (b + 1) * B)
            chosen += [b * B + p for p in commit(
                conf[at], self.open[at], int(self.passes[b]), cfg)]
            self.passes[b] += 1
        for offset, row in zip(chosen, logits_of(chosen)):
            self.rows[self.first + offset] = row
        self.masked[chosen] = False
        self.open[chosen] = False

    def result(self):
        return np.stack([self.rows[p] for p in range(self.prompt, self.n)])


def run(cfg, weights, sequences, spans, sequential=False):
    """Reference logits of a served generation, replayed.

    ``sequences``: prompt plus served tokens; ``spans``: for each,
    ``(prompt length - 1, length - 1)``, as ``benchmark/check.py``
    states a next-token span.  Returns, for each, one float32
    ``(generated, vocab)`` array: for every generated position the
    logits row, AT that position, of the pass that committed it.
    ``weights`` gives ``top()``, ``layer(i)`` and ``expert(i, e)`` as
    float32.  ``sequential``: one forward a block a pass, of the
    sequence so far (the plain form of Departure 1)."""
    eps = cfg["rms_norm_eps"]
    replays = [_Replay(cfg, tokens, span)
               for tokens, span in zip(sequences, spans)]
    with jax.default_matmul_precision(HIGHEST):
        top = weights.top()
        if sequential:
            for replay in replays:
                _sequential(cfg, weights, top, replay)
        while any(replay.unfinished() for replay in replays):
            busy = [replay for replay in replays if replay.unfinished()]
            states = hidden_states(cfg, weights, top,
                                   [replay.layout() for replay in busy])
            for replay, x in zip(busy, states):
                noisy = replay.length + np.arange(replay.length
                                                  - replay.first)
                conf = _over_head_rows(confidence, top, x, noisy, eps)
                replay.apply(
                    cfg, conf,
                    lambda chosen, x=x, at=replay.length: _logits(
                        top, x, at + np.asarray(chosen, int), eps),
                    replay.unfinished())
    return [replay.result() for replay in replays]


def _sequential(cfg, weights, top, replay):
    """The replay of one sequence, a block at a time: each pass ONE
    full forward of the sequence up to the block's end, the block as it
    stands."""
    B, eps = replay.B, cfg["rms_norm_eps"]
    for b in range(len(replay.passes)):
        base = replay.first + b * B
        while replay.open[b * B:(b + 1) * B].any():
            n = base + B
            masked = np.zeros(n, bool)
            masked[base:] = replay.masked[b * B:(b + 1) * B]
            position = np.arange(n)
            rows = _padded((replay.whole()[:n], masked, position,
                            np.full(n, CLEAN), position // B),
                           (0, False, 0, PADDING, 0))
            x, = hidden_states(cfg, weights, top, [rows])
            logits = _logits(top, x, np.arange(base, n), eps)
            shifted = logits - logits.max(-1, keepdims=True)
            conf = np.zeros(len(replay.masked), np.float32)
            conf[b * B:(b + 1) * B] = 1.0 / np.exp(shifted).sum(-1)
            replay.apply(cfg, conf,
                         lambda chosen: logits[np.asarray(chosen, int)
                                               - b * B], [b])
