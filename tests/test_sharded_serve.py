"""Sharded serving: the tp=2 ShardedSlotEngine must be INVISIBLE from the
outside — token-identical to the single-device SlotEngine across greedy /
sampled / speculative / chunked traffic, same page accounting, zero
recompiles after warmup — while the declarative rule layer underneath
(``parallel/rules.py``) resolves specs by table, not hand-wiring.

Runs on 2 of the 8 virtual CPU devices the conftest forces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_tpu.config import ServeConfig, validate_tp_mesh
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)
from distributed_tensorflow_tpu.parallel.rules import (
    SERVE_TP_RULES,
    TP_TRAIN_RULES,
    match_partition_rules,
)
from distributed_tensorflow_tpu.serve import ShardedSlotEngine, SlotEngine

pytestmark = [pytest.mark.serve, pytest.mark.sharded_serve]

CFG = TransformerConfig(
    vocab_size=64,
    d_model=32,
    num_heads=4,
    num_kv_heads=2,  # GQA on purpose: the kv-head axis IS the KV shard
    num_layers=2,
    d_ff=64,
    max_seq_len=64,
    compute_dtype=jnp.float32,
)

# One engine configuration exercises every decode program: speculative
# verify (greedy rounds), sampled fallback, chunked prefill for prompts
# past prefill_len, bucketed tail prefill + prefix adoption.
ENGINE_KW = dict(
    slots=3,
    max_len=64,
    prefill_len=16,
    page_size=8,
    prefix_cache=True,
    spec_k=2,
    prefill_buckets=(8,),
    prefill_chunk_tokens=8,
)


@pytest.fixture(scope="module")
def params():
    return TransformerLM(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


@pytest.fixture(scope="module")
def engines(params):
    """(single, sharded) pair, warmed once — the parity matrix, the page
    accounting and the healthz tests all drive the same two engines."""
    single = SlotEngine(CFG, params, **ENGINE_KW)
    single.warmup()
    sharded = ShardedSlotEngine(CFG, params, tp=2, **ENGINE_KW)
    sharded.warmup()
    return single, sharded


def _drive(engine, requests):
    """Chunk-aware closed-loop driver (PREFILLING starts return
    ``(None, False)``); asserts zero recompiles after warmup."""
    base = engine.compile_count()
    outs = {i: [] for i in range(len(requests))}
    pending = list(range(len(requests)))
    slot2req = {}
    while pending or slot2req:
        while pending:
            slot = engine.acquire_slot()
            if slot is None:
                break
            i = pending.pop(0)
            prompt, kwargs = requests[i]
            first, finished = engine.start(slot, prompt, **kwargs)
            if first is None:
                slot2req[slot] = i
            else:
                outs[i].append(first)
                if finished:
                    engine.release(slot)
                else:
                    slot2req[slot] = i
        if not slot2req:
            continue
        toks, valid, done = engine.step()
        for k in range(toks.shape[0]):
            for slot, i in slot2req.items():
                if valid[k, slot]:
                    outs[i].append(int(toks[k, slot]))
        for slot in list(slot2req):
            if done[slot]:
                engine.release(slot)
                del slot2req[slot]
    assert engine.compile_count() == base, (
        f"recompiled after warmup: {engine.compile_count()} != {base}"
    )
    return [tuple(outs[i]) for i in range(len(requests))]


_RNG = np.random.default_rng(11)
_SHARED = _RNG.integers(1, 64, 10).tolist()
_VARIANTS = {
    # all-greedy + shared prefix: speculative rounds + prefix adoption
    "greedy_spec": [
        (_SHARED + _RNG.integers(1, 64, int(t)).tolist(),
         {"max_new_tokens": b})
        for t, b in ((3, 8), (5, 6), (2, 10), (4, 7))
    ],
    # sampled lanes (rejection-sampling verify rounds) mixed with greedy
    "sampled": [
        (_RNG.integers(1, 64, 9).tolist(),
         {"max_new_tokens": 8, "temperature": 0.8, "top_k": 16, "seed": 1}),
        (_RNG.integers(1, 64, 12).tolist(),
         {"max_new_tokens": 6, "temperature": 1.1, "top_p": 0.9, "seed": 2}),
        (_RNG.integers(1, 64, 7).tolist(), {"max_new_tokens": 7}),
    ],
    # prompts past prefill_len=16: chunked prefill interleaved with decode
    "chunked": [
        (_RNG.integers(1, 64, 30).tolist(), {"max_new_tokens": 6}),
        (_RNG.integers(1, 64, 45).tolist(), {"max_new_tokens": 5}),
        (_RNG.integers(1, 64, 5).tolist(), {"max_new_tokens": 8}),
    ],
}


@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_sharded_token_parity(engines, variant):
    single, sharded = engines
    requests = _VARIANTS[variant]
    assert _drive(sharded, requests) == _drive(single, requests), (
        f"tp=2 engine diverged from single-device engine on {variant}"
    )


@pytest.fixture(scope="module")
def plain_engines(params):
    """The pair without speculation: plain decode rounds, which run ahead
    of the host's reading (a verify round never does)."""
    kw = dict(ENGINE_KW, spec_k=0)
    single = SlotEngine(CFG, params, **kw)
    single.warmup()
    sharded = ShardedSlotEngine(CFG, params, tp=2, **kw)
    sharded.warmup()
    return single, sharded


@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_sharded_token_parity_run_ahead(plain_engines, variant):
    """The sharded engine queues round n+1 from round n's registers as the
    single-device one does (replicated over its mesh, as its programs give
    them back: no recompile, ``_drive`` asserts) and serves its tokens."""
    single, sharded = plain_engines
    requests = _VARIANTS[variant]
    ahead0 = [e.stats["rounds_ahead"] for e in plain_engines]
    assert _drive(sharded, requests) == _drive(single, requests)
    ahead = [e.stats["rounds_ahead"] - a
             for e, a in zip(plain_engines, ahead0)]
    assert ahead[0] == ahead[1] > 0
    assert sharded.stats["plain_rounds"] == single.stats["plain_rounds"]


def test_page_accounting_matches_single_device(engines, params):
    """The pool's host-side bookkeeping must not know it is sharded:
    pages_free tracks the single engine's exactly through a churn, the
    page table stays host numpy, and releases leak nothing."""
    single, sharded = engines
    assert sharded.pool.pages_free == single.pool.pages_free
    assert isinstance(sharded.pool.page_tables, np.ndarray)
    # Prefix-cache-held pages legitimately stay bound between requests, so
    # take the leak baseline with both caches empty.
    for engine in (single, sharded):
        if engine.prefix is not None:
            engine.prefix.clear()
    free0 = sharded.pool.pages_free
    assert single.pool.pages_free == free0
    requests = _VARIANTS["greedy_spec"] + _VARIANTS["chunked"]
    for engine in (single, sharded):
        _drive(engine, requests)
    assert sharded.pool.pages_free == single.pool.pages_free
    for engine in (single, sharded):
        if engine.prefix is not None:
            engine.prefix.clear()
    assert sharded.pool.pages_free == free0
    assert single.pool.pages_free == free0
    # The KV buffers themselves really are split: half the kv heads live
    # on each device.
    k0 = sharded.pool.layers[0]["k"]
    shard_shapes = {s.data.shape for s in k0.addressable_shards}
    assert shard_shapes == {(k0.shape[0], CFG.kv_heads // 2) + k0.shape[2:]}


def test_sharded_constructor_guards(params):
    with pytest.raises(ValueError, match="tp >= 2"):
        ShardedSlotEngine(CFG, params, tp=1, **ENGINE_KW)
    with pytest.raises(ValueError, match="positive divisor of max_len"):
        kw = dict(ENGINE_KW, page_size=0)
        ShardedSlotEngine(CFG, params, tp=2, **kw)
    with pytest.raises(ValueError, match="num_kv_heads"):
        # kv_heads=2 cannot split 4 ways even though 8 devices exist
        ShardedSlotEngine(CFG, params, tp=4, **ENGINE_KW)
    with pytest.raises(ValueError, match="devices"):
        ShardedSlotEngine(
            CFG, params, tp=2, devices=jax.devices()[:1], **ENGINE_KW
        )


# -- declarative rules -----------------------------------------------------


def test_match_partition_rules_precedence_and_scalars():
    params = {
        "block": {"qkv": {"kernel": np.zeros((4, 12)),
                          "bias": np.zeros(12)}},
        "step": np.zeros(()),  # scalar: always replicated, rules unseen
    }
    rules = (
        (r"qkv/kernel$", P(None, "model")),  # first match wins...
        (r"qkv/", P("model")),
        (r".*", P()),
    )
    specs = match_partition_rules(rules, params)
    assert specs["block"]["qkv"]["kernel"] == P(None, "model")
    assert specs["block"]["qkv"]["bias"] == P("model")
    assert specs["step"] == P()
    # ...and order encodes precedence: the broad rule first shadows the
    # specific one.
    flipped = match_partition_rules(
        ((r"qkv/", P("model")), (r".*", P())), params)
    assert flipped["block"]["qkv"]["kernel"] == P("model")


def test_match_partition_rules_unmatched_path_raises():
    with pytest.raises(ValueError, match="Partition rule not found.*lonely"):
        match_partition_rules(
            ((r"qkv/kernel$", P(None, "model")),),
            {"lonely": {"kernel": np.zeros((2, 2))}},
        )


def test_serve_rules_on_real_param_tree(params):
    specs = match_partition_rules(SERVE_TP_RULES, params)
    b0 = specs["block_0"]
    assert b0["qkv"]["kernel"] == P(None, "model")
    assert b0["qkv"]["bias"] == P("model")
    assert b0["proj"]["kernel"] == P("model", None)
    assert b0["proj"]["bias"] == P()  # row-parallel bias: after the reduce
    assert b0["mlp_in"]["kernel"] == P(None, "model")
    assert b0["mlp_out"]["kernel"] == P("model", None)
    assert b0["ln1"]["scale"] == P()
    assert specs["tok_embed"]["embedding"] == P()
    assert specs["lm_head"]["kernel"] == P()


@pytest.mark.quant
@pytest.mark.parametrize("mode,gs", [("int8", 0), ("int4", 16)])
def test_serve_rules_on_quantized_param_tree(params, mode, gs):
    """Quantized leaves shard like the kernels they replace: column-parallel
    scales ride the out axis, int4 group scales ride their kernel's layout,
    and the row-parallel int8 scale stays replicated (it multiplies AFTER
    the tp all-reduce)."""
    from distributed_tensorflow_tpu.models.quant import quantize_lm_params

    qparams = quantize_lm_params(params, mode, group_size=gs, hp_dtype=None)
    specs = match_partition_rules(SERVE_TP_RULES, qparams)
    b0 = specs["block_0"]
    assert b0["qkv"]["kernel_q"] == P(None, "model")
    assert b0["mlp_in"]["kernel_q"] == P(None, "model")
    assert b0["proj"]["kernel_q"] == P("model", None)
    assert b0["mlp_out"]["kernel_q"] == P("model", None)
    if mode == "int8":
        assert b0["qkv"]["scale"] == P("model")
        assert b0["proj"]["scale"] == P()  # applied after the all-reduce
    else:
        assert b0["qkv"]["gscale"] == P(None, "model")
        assert b0["proj"]["gscale"] == P("model", None)
    assert specs["tok_embed"]["embedding"] == P()
    assert specs["lm_head"]["kernel"] == P()


def test_tp_train_rules_match_tp_param_specs():
    """The rules table IS tensor_parallel.tp_param_specs now — the fold
    must be observationally identical on a TpTransformerLM-shaped tree."""
    from distributed_tensorflow_tpu.parallel.tensor_parallel import (
        tp_param_specs,
    )

    tree = {
        "block_0": {
            "q": {"kernel": np.zeros((4, 4)), "bias": np.zeros(4)},
            "proj": {"kernel": np.zeros((4, 4))},
            "proj_bias": np.zeros(4),
            "mlp_in": {"kernel": np.zeros((4, 8)), "bias": np.zeros(8)},
            "mlp_out": {"kernel": np.zeros((8, 4))},
            "ln1": {"scale": np.zeros(4)},
        },
        "tok_embed": {"embedding": np.zeros((16, 4))},
    }
    assert tp_param_specs(tree) == match_partition_rules(
        TP_TRAIN_RULES, tree)


# -- config validation -----------------------------------------------------


def test_serve_config_rejects_tp_not_dividing_kv_heads():
    with pytest.raises(ValueError, match="does not divide num_kv_heads"):
        ServeConfig(tp=3).validate_mesh(CFG)  # kv_heads=2, 2 % 3 != 0


def test_serve_config_rejects_tp_not_dividing_d_model():
    # kv divides (4 % 4 == 0) so the d_model check is what fires.
    from types import SimpleNamespace

    shapes = SimpleNamespace(kv_heads=4, d_model=30)
    with pytest.raises(ValueError, match="does not divide d_model"):
        validate_tp_mesh(shapes, 4)
    with pytest.raises(ValueError, match="does not divide d_model"):
        ServeConfig(tp=2).validate_mesh(
            SimpleNamespace(kv_heads=2, d_model=33))
    # tp=1 is always a no-op, whatever the shapes.
    assert ServeConfig(tp=1).validate_mesh(shapes) is None


@pytest.mark.quant
def test_serve_config_validate_quant():
    """Config-time quant validation, beside the tp-mesh checks it mirrors:
    every rejection names the offending flag pair and what would fix it."""
    # off = no-op, whatever the shapes
    assert ServeConfig().validate_quant(CFG) is None
    # group_size without a mode: nothing to group
    with pytest.raises(ValueError, match="quant_group_size"):
        ServeConfig(quant_group_size=16).validate_quant(CFG)
    # int8 is per-channel — grouping does not apply
    with pytest.raises(ValueError, match="int8"):
        ServeConfig(weight_dtype="int8",
                    quant_group_size=16).validate_quant(CFG)
    # int4 requires a group size...
    with pytest.raises(ValueError, match="group"):
        ServeConfig(weight_dtype="int4").validate_quant(CFG)
    # ...that divides both matmul reduction dims (d_model=32, d_ff=64)
    with pytest.raises(ValueError, match="divide"):
        ServeConfig(weight_dtype="int4",
                    quant_group_size=24).validate_quant(CFG)
    # unknown mode names the accepted ones
    with pytest.raises(ValueError, match="int8"):
        ServeConfig(weight_dtype="fp8").validate_quant(CFG)
    # int4 under tp: per-shard reduction dims must still group evenly
    with pytest.raises(ValueError, match="tp"):
        ServeConfig(weight_dtype="int4", quant_group_size=32,
                    tp=2).validate_quant(CFG)
    # valid configs pass
    assert ServeConfig(weight_dtype="int8").validate_quant(CFG) is None
    assert ServeConfig(weight_dtype="int4",
                       quant_group_size=16).validate_quant(CFG) is None


# -- healthz / registry topology -------------------------------------------


def test_healthz_and_probe_report_mesh(engines):
    import json
    import threading
    import urllib.request

    from distributed_tensorflow_tpu.serve import Scheduler, ServingMetrics
    from distributed_tensorflow_tpu.serve.fleet.registry import http_probe
    from distributed_tensorflow_tpu.serve.server import make_server

    single, sharded = engines
    for engine, want_tp in ((sharded, 2), (single, 1)):
        sched = Scheduler(engine, max_queue_depth=4,
                          metrics=ServingMetrics())
        server = make_server(sched, port=0, request_timeout_s=10.0)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            host, port = server.server_address
            base = f"http://{host}:{port}"
            with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
                body = json.loads(r.read())
            dev = jax.devices()[0]
            assert body["mesh"] == {
                "tp": want_tp, "devices": want_tp,
                "platform": dev.platform, "device_kind": dev.device_kind,
            }
            assert body["weight_dtype"] == "native"  # CFG is unquantized
            probe = http_probe(base, timeout_s=10.0)
            assert probe.ok and probe.tp == want_tp
            assert probe.devices == want_tp
            assert probe.weight_dtype == "native"
        finally:
            server.shutdown()
            server.server_close()
            thread.join(timeout=5)
