"""The plain decode floor (benchmark/lib/roofline.py `decode_bytes`, read
by benchmark/readers/device_trace.py `decode_hbm_share`) counts the
experts a step's lanes VISITED: the weights in two parts at the published
shapes of the accepted configurations, the need at both ends of the
visited count, and the reader where the program does not count visits.
The weight term is one function for every decode floor: the family
floors' results are held to the expression they had, bit for bit."""

import jax
import pytest

from benchmark.lib import (moe_floors, recurrent_floors, roofline,
                           sparse_floors, spec, ssm_floors)
from benchmark.lib.model import source_keys
from benchmark.readers import device_trace


def described(cell_name):
    """`roofline.describe` for a cell's configuration file at the shapes
    it is run at, from the program's own `init_params` under
    `jax.eval_shape`: no weight is made."""
    from dynamo_tpu.models import get_family

    cell = spec.load_cell(cell_name)
    config = cell["config"]
    klass = spec.model_class(config)
    cfg = klass.program_config(source_keys(config, False),
                               cell["config_entry"]["name"])
    family = get_family(cfg)
    params = jax.eval_shape(lambda key: family.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    return (roofline.describe(params, cfg, family,
                              config["engine"]["block_size"],
                              klass.attn_pair_flops(cfg)),
            params, config)


def test_a_configuration_without_experts_keeps_its_floor():
    r, params, _ = described("mistral-7b.chat")
    assert r["expert_bytes"] == 0.0
    assert r["dense_weight_bytes"] == roofline.weight_bytes_per_step(params)
    assert r["dense_weight_bytes"] == r["weight_bytes"] == 7248297984.0
    assert r["kv_bytes_per_token"] == 16 * 2 * 8 * 128 * 2


def test_moonlights_weights_in_two_parts():
    r, params, hf = described("moonlight-16b.chat")
    # one routed expert: gate, up and down of hidden x moe_intermediate
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    assert (d, f) == (2048, 1408)
    assert r["expert_bytes"] == 3 * d * f * 2 == 3 * 2048 * 1408 * 2
    slots = hf["n_routed_experts"] * (hf["num_hidden_layers"]
                                      - hf["first_k_dense_replace"])
    assert slots == 448
    assert r["dense_weight_bytes"] + slots * r["expert_bytes"] == \
        roofline.weight_bytes_per_step(params) == r["weight_bytes"]
    # the cache a token holds: latent 512 + rope key 64, 8 layers, bf16
    assert r["kv_bytes_per_token"] == 8 * (512 + 64) * 2


PARTS = {"dense_weight_bytes": 1000.0, "expert_bytes": 100.0,
         "kv_bytes_per_token": 10.0}
R = dict(PARTS, weight_bytes=1000.0 + 7 * 100.0)    # seven expert slots


@pytest.mark.parametrize("visited,want", [
    (12 * 7, 12 * (1700.0 + 10.0 * 50)),     # all seven, every step: the old need
    (0, 12 * (1000.0 + 10.0 * 50)),          # none: the dense part and the cache
    (30, 12 * 1000.0 + 30 * 100.0 + 12 * 10.0 * 50)])
def test_need_at_both_ends_of_the_visited_count(visited, want):
    need = roofline.decode_bytes(12, visited, 50.0, **PARTS)
    assert need == want
    if visited == 12 * 7:       # what the floor was: every weight a step
        assert need == 12 * (R["weight_bytes"] + 10.0 * 50)


def ctx(close, roof=R):
    return {"trace": {"kind_s": {"decode": 0.02, "prefill": 0.01}},
            "trace_window": (100.0, 100.1), "mono_offset": 0.0, "chips": 1,
            "fpm": [{"kind": "decode", "k": 8, "t": 100.01},
                    {"kind": "decode", "k": 4, "t": 100.05},
                    {"kind": "decode", "k": 8, "t": 99.0}],
            "trace_counters": [{"moe_experts_visited.decode": 10,
                                "prefill_tokens": 5}, close],
            "records": [], "peaks": {"hbm_bytes_per_s": 1e6},
            "roofline": roof}


def test_reader_counts_the_visited_experts():
    # 12 steps in the stretch, 30 visits, no request decoding: no cache
    got = device_trace.decode_hbm_share(
        ctx({"moe_experts_visited.decode": 40}), "decode")
    assert got == pytest.approx(100 * (12000.0 + 3000.0) / 0.02 / 1e6)


def test_experts_and_no_counter_reads_nothing():
    """Never the number that counts every expert: None, and the harness
    leaves the metric out of the line."""
    assert device_trace.decode_hbm_share(
        ctx({"prefill_tokens": 9}), "decode") is None
    # without experts the same program reads its dense weights
    dense = dict(R, expert_bytes=0.0)
    assert device_trace.decode_hbm_share(
        ctx({"prefill_tokens": 9}, dense), "decode") == pytest.approx(
            100 * 12000.0 / 0.02 / 1e6)


def test_a_counts_member_is_not_cache():
    """A family that carries its device-side counts as the cache tuple's
    last member (a vector) holds the same cache bytes a token."""
    planes = [(8, 1, 1, 512, 128), (8, 1, 1, 64, 128)]
    assert roofline.kv_bytes_per_token(planes, 128, 2) == 8 * 576 * 2
    assert roofline.kv_bytes_per_token(planes + [(3,)], 128, 2) == \
        roofline.kv_bytes_per_token(planes, 128, 2)


def test_expert_layers_of_unequal_size_are_refused():
    class Leaf:
        def __init__(self, *shape):
            self.shape, self.dtype = shape, jax.numpy.dtype("bfloat16")

    layers = [{"moe_w_up": Leaf(4, 8, 16), "moe_w_down": Leaf(4, 16, 8)},
              {"moe_w_up": Leaf(4, 8, 16), "moe_w_down": Leaf(4, 16, 8)}]
    assert roofline.weight_parts({"layers": layers}) == (0.0, 2 * 8 * 16 * 2)
    layers[1]["moe_w_up"] = Leaf(4, 8, 32)
    with pytest.raises(ValueError):
        roofline.weight_parts({"layers": layers})


WEIGHTS = dict(dense_weight_bytes=2965372928.0, expert_bytes=50331648.0)
STEPS, VISITED = 337.0, 17011.0


@pytest.mark.parametrize("floor,rest,tail", [
    (moe_floors.decode_bytes,
     dict(global_layers=2, window_layers=5, global_block_bytes=327680.0,
          window_block_bytes=655360.0),
     lambda a, b, k: (a * k["global_layers"] * k["global_block_bytes"],
                      b * k["window_layers"] * k["window_block_bytes"])),
    (sparse_floors.decode_bytes,
     dict(layers=12, index_key_bytes=128.0, kv_token_bytes=2048.0),
     lambda a, b, k: (k["layers"] * (a * k["index_key_bytes"]
                                     + b * k["kv_token_bytes"]),)),
    (recurrent_floors.decode_bytes,
     dict(lane_step_bytes=43417600.0, latent_token_bytes=2304.0),
     lambda a, b, k: (a * k["lane_step_bytes"],
                      b * k["latent_token_bytes"])),
    (ssm_floors.decode_bytes,
     dict(lane_step_bytes=51216384.0, kv_token_bytes=4096.0),
     lambda a, b, k: (a * k["lane_step_bytes"], b * k["kv_token_bytes"]))],
    ids=["moe", "sparse", "recurrent", "ssm"])
def test_family_floors_keep_their_numbers_bit_for_bit(floor, rest, tail):
    """Each family floor is `steps x dense + visited x expert` plus its
    own terms, added in the order they always were."""
    a, b = 123457.0, 98765.5
    want = STEPS * WEIGHTS["dense_weight_bytes"] \
        + VISITED * WEIGHTS["expert_bytes"]
    for term in tail(a, b, rest):
        want = want + term
    assert floor(STEPS, VISITED, a, b, **WEIGHTS, **rest) == want
