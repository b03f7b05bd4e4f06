"""Operations and bytes of the GPT-2 work, computed from shapes alone:
the same work reads the same whatever implements it. `m` is the model
section of a configuration file (HF GPT-2 key names)."""


def inner(m):
    return m.get('n_inner') or 4 * m['n_embd']


def n_params(m):
    """All parameters: tied token embedding (also the head), positions,
    per layer qkv/out/mlp weights and biases and two LayerNorms, ln_f."""
    d, f = m['n_embd'], inner(m)
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (d * f + f) \
        + (f * d + d) + 4 * d
    return m['vocab_size'] * d + m['n_positions'] * d \
        + m['n_layer'] * per_layer + 2 * d


def n_matmul_params(m):
    """Parameters a token multiplies in a forward pass: the layers'
    matrices and the tied head; the embedding lookup and the position
    table are reads, not multiplications."""
    d, f = m['n_embd'], inner(m)
    return m['n_layer'] * (4 * d * d + 2 * d * f) + m['vocab_size'] * d


def weight_bytes(m, itemsize=2):
    return n_params(m) * itemsize


def kv_bytes_per_token(m, itemsize=2):
    """K and V rows of one token over all layers."""
    return 2 * m['n_layer'] * m['n_embd'] * itemsize


def train_flops_per_token(m, seq_len):
    """Forward + backward of one token at sequence length `seq_len`:
    6 N + 12 L H S (the arithmetic of `bench.py`/`flops_per_token`);
    recomputed operations are not counted."""
    return 6 * n_params(m) + 12 * m['n_layer'] * m['n_embd'] * seq_len


def serve_flops_token(m, context):
    """Forward of one token that attends to `context` held tokens: two
    operations per multiplied parameter plus QK^T and PV over the
    context (4 L H per held token)."""
    return 2 * n_matmul_params(m) + 4 * m['n_layer'] * m['n_embd'] * context


def decode_step_least_seconds(m, contexts, peak_flops, peak_bw, itemsize=2):
    """Least time of ONE decode step for rows holding `contexts` tokens:
    max(FLOPs / peak, bytes / bandwidth) with bytes = the weights once +
    the K/V of the tokens HELD (never the logical capacity). Returns
    (seconds, 'compute' | 'bandwidth')."""
    flops = sum(serve_flops_token(m, c) for c in contexts)
    nbytes = weight_bytes(m, itemsize) \
        + kv_bytes_per_token(m, itemsize) * sum(contexts)
    tc, tb = flops / peak_flops, nbytes / peak_bw
    return max(tc, tb), ('compute' if tc >= tb else 'bandwidth')


def flash_least_seconds(m, batch, seq_len, peak_flops, peak_bw, calls,
                        itemsize=2):
    """Least time of the causal flash-attention kernels of ONE layer of
    one step. `calls` = (forward calls, backward calls) executed per layer
    (recomputation runs the forward twice). Forward: QK^T and PV over the
    causal half = 2 B H S^2 D operations, reads q,k,v and writes o.
    Backward: five such products = 5 B H S^2 D, reads q,k,v,o,do and
    writes dq,dk,dv."""
    d = m['n_embd']                      # H * D
    sq = batch * seq_len * seq_len * d
    act = batch * seq_len * d * itemsize
    fwd = max(2 * sq / peak_flops, 4 * act / peak_bw)
    bwd = max(5 * sq / peak_flops, 8 * act / peak_bw)
    return calls[0] * fwd + calls[1] * bwd
