"""Model FLOPs and the scan's own work for the Mamba-2/attention hybrid, in
closed form from the configuration's published keys.

Counted as ``flops.py`` counts: a multiply and an add are two operations,
only matrix work counts, backward is twice the forward, recomputed
operations never count.  The state-space scan is counted in its chunked
(SSD) form at the published chunk size, with the causal half of the
in-chunk products, as ``flops.gpt_forward`` halves attention.  These
functions count the same work whatever implements the scan, so a share
computed from them cannot pass 100% and does not move when the
implementation does.
"""


def ssd_forward_flops(cfg):
    """Forward FLOPs of the scan for one token of one Mamba-2 layer."""
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    hp = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    groups = cfg["mamba_n_groups"]
    return (2 * (q // 2) * n * groups   # C B^T, the causal half
            + 2 * (q // 2) * hp         # the masked product with x
            + 2 * n * hp                # the state a chunk leaves behind
            + 2 * n * hp)               # reading the entering state out


def ssd_train_flops(cfg, tokens):
    """Forward plus backward, counted once, over every Mamba-2 layer."""
    return (3 * ssd_forward_flops(cfg) * tokens
            * cfg["layer_types"].count("mamba"))


def ssd_train_bytes(cfg, tokens, itemsize=2):
    """Bytes the scan has to move in a step whatever implements it: forward
    one read of ``x``, ``dt``, ``B``, ``C`` and one write of ``y``; backward
    one read of those and of ``dy`` and one write of the four gradients.
    ``x``, ``B``, ``C``, ``y`` in the compute dtype, ``dt`` in float32."""
    hp = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    bc = 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    inputs = (hp + bc) * itemsize + cfg["mamba_n_heads"] * 4
    y = hp * itemsize
    per_token = (inputs + y) + (inputs + y + inputs)
    return per_token * tokens * cfg["layer_types"].count("mamba")


def forward(cfg, seq):
    """Forward FLOPs of one sequence of ``seq`` tokens: projections, MLPs,
    the scan's products, causal attention (half of ``seq x seq``) and the
    tied head.  The depthwise convolution, norms, gates and the embedding
    look-up are not matrix work."""
    d, n, groups = cfg["hidden_size"], cfg["mamba_d_state"], cfg["mamba_n_groups"]
    d_inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    head = d // cfg["num_attention_heads"]
    mlp = 3 * d * cfg["shared_intermediate_size"]       # parameters
    mamba = (d * (2 * d_inner + 2 * groups * n + cfg["mamba_n_heads"])
             + d_inner * d + mlp)
    attention = (2 * d * d + 2 * d * head * cfg["num_key_value_heads"] + mlp)
    per_token = {
        "mamba": 2 * mamba + ssd_forward_flops(cfg),
        "attention": 2 * attention + 2 * seq * d,       # QK^T and PV, halved
    }
    return seq * (sum(per_token[kind] for kind in cfg["layer_types"])
                  + 2 * d * cfg["vocab_size"])


def train(cfg, batch, seq):
    return 3 * batch * forward(cfg, seq)
