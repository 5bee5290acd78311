"""Operation and byte counts against hand counts for one GPT-2 layer, dense
and pruned."""
import counts

LAYER = {"n_layer": 1, "n_embd": 768, "n_head": 12, "head_dim": 64,
         "n_inner": 3072, "vocab_size": 50257}


def test_matmul_and_attention_operations_of_one_layer():
    d, f, v = 768, 3072, 50257
    qkv, out, mlp = 2 * d * 3 * d, 2 * d * d, 2 * (2 * d * f)
    assert counts.matmul_flops_per_token(LAYER) == qkv + out + mlp
    # scores and weighted values over 100 keys, 12 heads of 64
    assert counts.attn_flops(LAYER, 100) == 2 * (2 * 100 * 12 * 64)
    assert counts.head_flops(LAYER) == 2 * d * v
    s = 5   # causal prompt: query i attends i + 1 keys; head at the end
    assert counts.prefill_flops(LAYER, s) == (
        s * (qkv + out + mlp) + sum(4 * 768 * (i + 1) for i in range(s))
        + 2 * d * v)


def test_decode_steps_of_one_layer():
    import numpy as np
    d, f, v = 768, 3072, 50257
    weights = (v * d + 2 * d                       # table, final norm
               + 4 * d * d + 2 * d                 # q, k, v, o; norm
               + 2 * d * f + f + d + 2 * d)        # MLP, biases; norm
    kv_pos = 2 * d * 2                             # K and V in bf16
    qkv, out, mlp = 2 * d * 3 * d, 2 * d * d, 2 * (2 * d * f)
    # one step whose two active slots feed positions 10 and 20, and an
    # empty step
    flops, nbytes = counts.decode_steps(LAYER, np.array([2, 0]),
                                        np.array([30, 0]))
    assert nbytes[0] == (2 * weights + 2 * 2 * d   # weights, position rows
                         + (10 + 20) * kv_pos      # K/V held
                         + 2 * kv_pos)             # one new position each
    assert flops[0] == 2 * (qkv + out + mlp + 2 * d * v) + sum(
        4 * 768 * (p + 1) for p in (10, 20))
    assert (flops[1], nbytes[1]) == (0, 2 * weights)


def test_pruned_layer_counts_only_what_it_keeps():
    member = dict(LAYER, layers=[{"heads": 1, "n_inner": 0}])
    d = 768
    assert counts.matmul_flops_per_token(member) == 8 * d * 64
    assert counts.kv_bytes_per_position(member) == 2 * 64 * 2
    dropped = dict(LAYER, layers=[{"heads": 0, "n_inner": 0}])
    assert counts.matmul_flops_per_token(dropped) == 0
    assert counts.weight_elems(dropped) == 50257 * d + 2 * d
