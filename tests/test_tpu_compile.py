"""Kernels of the serving path, compiled for a described (not attached)
TPU v5e at the benchmark's widths: what Mosaic refuses — a slice off the
tiling, too much VMEM — interpret mode on the CPU never sees.

Nothing runs and nothing is timed. The topology is described inside a
fixture, by the one test worker that is handed this file (only one
process at a time may load the TPU's library); every such compile of the
repository belongs in THIS file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_tpu.ops.attention import (
    paged_decode_attention,
    paged_decode_chain,
)

pytestmark = [pytest.mark.serve, pytest.mark.paged]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _kernel_line(compiled) -> str:
    """The custom call of the kernel in an optimised module's text."""
    (line,) = [l for l in compiled.as_text().splitlines()
               if "tpu_custom_call" in l and "custom-call(" in l]
    return line


# The paged kernel's copy chain (PR 38) waits for a whole chunk with ONE
# descriptor a leaf of the buffer's shape and copies a step of neighbouring
# pages with one. Tier-1 runs the kernel in interpret mode, where a copy is
# done when it is started and a wait of the wrong byte count cannot hang:
# what holds the byte counts is that PR's run on the chip (PERF.md, PR 38),
# and here only that Mosaic takes the chain at the cells' shapes, inside the
# VMEM it is given (it refuses a kernel over the limit).
@pytest.mark.parametrize("dtype,slots,kv,group,ps,pps,window,chain,result", [
    # starcoder2-3b as benchmarks/configs runs it: 16 slots of 4096, GQA
    # 24 / 2, pages of 16, the window equal to serve_max_len. Group 12
    # stays on the form it has: q padded to 16 rows a head, bf16 out; a
    # page of 8 KiB makes chunks of 128 pages and steps of 16.
    (jnp.bfloat16, 16, 2, 12, 16, 256, 4096, (128, 16), "bf16[16,2,16,128]"),
    # zaya1-8b: 32 slots, 4 query rows a kv head, no window.
    (jnp.bfloat16, 32, 2, 4, 16, 256, None, (128, 16), "bf16[32,2,16,128]"),
    # nemotron3-nano-30b's attention layer: 64 slots, 16 rows a kv head.
    (jnp.bfloat16, 64, 2, 16, 16, 256, None, (128, 16), "bf16[64,2,16,128]"),
    # An f32 pool at its own tile, MHA, a window that skips pages: the
    # row form, flat f32 rows out; a row of 12 pages is one chunk.
    (jnp.float32, 4, 2, 1, 8, 12, 20, (12, 8), "f32[4,2,128]"),
], ids=["sc2-3b-bf16", "zaya1-8b-bf16", "nemotron3-nano-bf16",
        "f32-page8-window"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, dtype, slots, kv,
                                              group, ps, pps, window, chain,
                                              result):
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pages = slots * pps + 1
    assert paged_decode_chain(arg((pages, kv, ps, 128), dtype), pps) == chain
    compiled = jax.jit(
        lambda q, k, v, tables, lens: paged_decode_attention(
            q, k, v, tables, lens, window=window, interpret=False)
    ).lower(
        arg((slots, kv, group, 128), dtype),
        arg((pages, kv, ps, 128), dtype),
        arg((pages, kv, ps, 128), dtype),
        arg((slots, pps), jnp.int32),
        arg((slots,), jnp.int32),
    ).compile()
    assert f"= {result}" in _kernel_line(compiled)


@pytest.mark.parametrize("pages_per_chunk", [None, 32],
                         ids=["chain8", "chunk32"])
def test_paged_decode_kernel_compiles_for_evabyte_rows(one_chip,
                                                       pages_per_chunk):
    """evabyte-6.5b as benchmarks/configs runs it: 16 slots, 32 kv heads of
    128 and no groups, pages of 16, a composed row of 248 pages (15 windows
    of summaries and one window of K/V rows): the row form, at the 8 pages
    a chunk and one page a copy that the chain takes for a page of 128 KiB
    (16 times StarCoder2's), and at 32 pages a chunk, whose 16 MiB of chunk
    buffers fit only because the call asks Mosaic for the VMEM its shapes
    need (Mosaic refuses a kernel over the limit it was given)."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    slots, kv, ps, pps = 16, 32, 16, 248
    pages = slots * (pps + 8) + 1
    assert paged_decode_chain(
        arg((pages, kv, ps, 128), jnp.bfloat16), pps) == (8, 1)
    compiled = jax.jit(
        lambda q, k, v, tables, lens: paged_decode_attention(
            q, k, v, tables, lens, pages_per_chunk=pages_per_chunk,
            interpret=False)
    ).lower(
        arg((slots, kv, 1, 128), jnp.bfloat16),
        arg((pages, kv, ps, 128), jnp.bfloat16),
        arg((pages, kv, ps, 128), jnp.bfloat16),
        arg((slots, pps), jnp.int32),
        arg((slots,), jnp.int32),
    ).compile()
    assert "= f32[16,32,128]" in _kernel_line(compiled)


def _zaya_layer(one_chip, layers=1):
    """ZAYA1's block at the published widths (benchmarks/configs), cut to
    ``layers`` layers and a 4,096-row vocabulary so that it compiles in
    seconds: the model, its parameters as shapes on the described chip."""
    import json

    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "zaya1-8b.json")
    with open(path) as fh:
        mcfg = dict(json.load(fh)["transformer_config"],
                    num_layers=layers, vocab_size=4096)
    cfg = TransformerConfig(**mcfg, compute_dtype=jnp.bfloat16)
    model = TransformerLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip), shapes)
    return cfg, model, params


def test_zaya_decode_round_compiles_for_v5e(one_chip, monkeypatch):
    """One layer of the decode round as ``zaya1-8b.reason-closed`` runs it:
    32 slots of 4096, pages of 16. The expert products lower to the
    grouped-matmul kernel (two ``grouped_matmul`` custom calls a layer,
    ``ops/grouped_matmul.py``), attention to the paged kernel at 4 query rows
    a kv head, and no program holds a (tokens, experts, width) product."""
    from distributed_tensorflow_tpu.models.decoding import decode_step

    # The paged kernel asks the default backend whether to interpret; here
    # that is the CPU, and the program under test is the chip's.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, model, params = _zaya_layer(one_chip)
    slots, ps, pps = 32, 16, 256

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = [{"k": arg((slots * pps + 1, 2, ps, 128), jnp.bfloat16),
             "v": arg((slots * pps + 1, 2, ps, 128), jnp.bfloat16),
             "cca": arg((slots, cfg.cca_hist, cfg.cca_state_width),
                        jnp.bfloat16)}]

    def step(params, pool, tables, active, lengths, tok):
        dest = tables[jnp.arange(slots), lengths // ps]
        cache = {"layers": pool, "len": lengths, "pages": tables,
                 "write_page": jnp.where(active, dest, 0),
                 "attend": jnp.where(active, lengths + 1, 0),
                 "n_real": active.astype(jnp.int32),
                 "route_mask": active[:, None]}
        cache, logits = decode_step(model, params, cache, tok[:, None])
        return cache["layers"], logits.argmax(-1), cache["moe_counts"]

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, arg((slots, pps), jnp.int32), arg((slots,), jnp.bool_),
        arg((slots,), jnp.int32), arg((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    # The instruction's own name and result, left of its "custom-call(".
    calls = [l.split(" custom-call(")[0].strip() for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    products = [c for c in calls if c.startswith("%grouped_matmul")]
    assert len(products) == 2 and all("f32[32," in c for c in products)
    assert "ragged-dot" not in text
    # The paged kernel, its 4 query rows a kv head padded to 16.
    assert sum("bf16[32,2,16,128]" in c for c in calls) == 1
    assert "[32,16," not in text  # nothing dense over all experts


def test_zaya_prefill_chunk_costs_its_tokens_not_sixteen_times(one_chip):
    """A 1024-wide prefill chunk of one layer: the compiler's own count of
    the program's FLOPs is that of 1024 tokens through ONE expert (25.8
    GFLOP), the latent attention over 5120 rows and the projections, about
    60 GFLOP; dense over all 16 experts would be 412 GFLOP in the experts
    alone. And the head is formed for one row, not for 1024."""
    cfg, model, params = _zaya_layer(one_chip)
    width, rows = 1024, 4096 + 1024

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(params, k, v, state, tokens, n_real, start):
        cache = {"layers": [{"k": k, "v": v, "cca": state[None]}],
                 "len": start, "n_real": n_real[None],
                 "route_mask": (jnp.arange(width) < n_real)[None]}
        logits, cache = model.apply(
            {"params": params}, tokens, cache=cache,
            logit_rows=(n_real - 1)[None])
        return logits, cache["layers"][0]["cca"]

    compiled = jax.jit(chunk).lower(
        params, arg((1, 2, rows, 128), jnp.bfloat16),
        arg((1, 2, rows, 128), jnp.bfloat16),
        arg((cfg.cca_hist, cfg.cca_state_width), jnp.bfloat16),
        arg((1, width), jnp.int32), arg((), jnp.int32),
        arg((), jnp.int32)).compile()
    flops = compiled.cost_analysis()["flops"]
    one_expert = 2 * 3 * 2048 * 2048 * width
    assert one_expert < flops < 4 * one_expert, flops
    assert f"f32[1,1,{cfg.vocab_size}]" in compiled.as_text()
    assert f"[1,{width},{cfg.vocab_size}]" not in compiled.as_text()


def test_sc2_prefill_chunk_holds_no_score_matrix(one_chip, monkeypatch):
    """``starcoder2-3b``'s ``prefill_fn`` as the two ``sc2-3b`` cells build
    it (16 slots of 4096, pages of 16, one bucket of 1024; cut to one layer
    and a 4,096-row vocabulary so that it compiles in seconds), lowered for
    the v5e: the chunk attends through the ``prefill_chunk_attention``
    custom call at 12 query heads a kv head, and the program holds no
    float32 array of (..., 1024, 4096): the dense lines' scores, 403 MB a
    layer, and their softmax."""
    import json
    import re

    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from distributed_tensorflow_tpu.serve.engine import SlotEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "starcoder2-3b.json")
    with open(path) as fh:
        conf = json.load(fh)
    mcfg = dict(conf["transformer_config"], num_layers=1, vocab_size=4096)
    cfg = TransformerConfig(**mcfg, compute_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    serve = conf["serve_config"]
    engine = SlotEngine(
        cfg,
        jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.bfloat16), shapes),
        slots=serve["slots"], max_len=serve["serve_max_len"],
        prefill_len=serve["prefill_len"],
    )
    assert engine.prefill_path == "flash"
    assert engine.prefill_buckets == (1024,) and engine.page_size == 16

    def arg(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one_chip)
    compiled = engine._prefill_greedy.lower(
        jax.tree_util.tree_map(arg, engine.pool.layers),
        jax.tree_util.tree_map(arg, engine.params),
        jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one_chip),
        scalar(jnp.int32), scalar(jnp.int32),
        arg(engine.pool.page_tables[0]),
        scalar(jnp.float32), scalar(jnp.int32), scalar(jnp.float32),
        scalar(jnp.uint32),
    ).compile()
    text = compiled.as_text()
    (call,) = [l.split(" custom-call(")[0].strip() for l in text.splitlines()
               if "tpu_custom_call" in l and " custom-call(" in l]
    assert call.startswith("%prefill_chunk_attention")
    assert "bf16[2,12,1024,128]" in call
    assert not re.search(r"f32\[[\d,]*1024,4096\]", text)


def _one_layer(one_chip, name, **over):
    """A configuration of ``benchmarks/configs`` cut to one layer (and the
    overrides), its model, and its parameters as bf16 shapes on the
    described chip."""
    import json

    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", name + ".json")
    with open(path) as fh:
        mcfg = dict(json.load(fh)["transformer_config"], num_layers=1, **over)
    cfg = TransformerConfig(**mcfg, compute_dtype=jnp.bfloat16)
    model = TransformerLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip), shapes)
    return cfg, model, params


def _row_writes_in_place(text, leaf):
    """The custom calls ``paged_row_write`` of an optimised module, after
    checking that no scatter and no copy of a pool ``leaf`` (its shape, or
    the rows-of-dh view the scatter took) stands in the module."""
    import re

    n, kv, ps, dh = leaf
    for shape in (f"bf16[{n},{kv},{ps},{dh}]", f"bf16[{n * kv * ps},{dh}]"):
        assert not re.search(re.escape(shape) + r"\S* copy\(", text), shape
    assert " scatter(" not in text and "scatter_" not in text
    alias = text[text.index("input_output_alias"):].split("\n")[0]
    assert alias.count("may-alias") + alias.count("must-alias") >= 2
    return [l.split(" custom-call(")[0].strip() for l in text.splitlines()
            if "tpu_custom_call" in l and " custom-call(" in l
            and l.strip().startswith("%paged_row_write")]


@pytest.mark.parametrize("name,kv,calls", [
    # evabyte.sessions-closed: the rows, and the summaries of the lanes that
    # fill a chunk, 32 kv heads of 128 a page.
    ("evabyte-6.5b", 32, 2),
    # sc2-3b's cells: 16 slots of 4096, 2 kv heads.
    ("starcoder2-3b", 2, 1),
], ids=["evabyte", "sc2-3b"])
def test_decode_round_writes_rows_by_page_copy(one_chip, monkeypatch, name,
                                               kv, calls):
    """One layer of the decode round as the cell runs it (16 slots, pages of
    16, 4097 pages a leaf), its pool donated: the new rows go through
    ``paged_row_write`` custom calls, with no scatter in the module and no
    copy of a pool leaf, so the leaves are written where they lie."""
    from distributed_tensorflow_tpu.models.decoding import decode_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eva = name == "evabyte-6.5b"
    cfg, model, params = _one_layer(
        one_chip, name, **({} if eva else {"vocab_size": 4096}))
    slots, ps, pps = 16, 16, 256
    leaf = (slots * pps + 1, kv, ps, 128)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = [{"k": arg(leaf, jnp.bfloat16), "v": arg(leaf, jnp.bfloat16)}]

    def step(params, pool, tables, active, lengths, tok):
        lanes = jnp.arange(slots)
        dest = tables[lanes, lengths // ps]
        cache = {"layers": pool, "len": lengths, "pages": tables,
                 "write_page": jnp.where(active, dest, 0),
                 "attend": jnp.where(active, lengths + 1, 0)}
        if eva:  # the forming page's entry, as eva_table_forward finds it
            cache["sum_page"] = tables[lanes, pps - 1]
        cache, logits = decode_step(model, params, cache, tok[:, None])
        return cache["layers"], logits.argmax(-1)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, arg((slots, pps), jnp.int32), arg((slots,), jnp.bool_),
        arg((slots,), jnp.int32), arg((slots,), jnp.int32)).compile()
    writes = _row_writes_in_place(compiled.as_text(), leaf)
    assert len(writes) == calls
    assert all(f"bf16[{leaf[0]},{kv},16,128]" in w for w in writes)


def _nemotron_layers(one_chip, pattern):
    """The Nemotron-H stage at the published widths (benchmarks/configs), cut
    to the layers of ``pattern`` and an 8,192-row vocabulary (4,096 is the
    Mamba mixer's width) so that it compiles in seconds: the model, its parameters as shapes on the
    described chip."""
    import json

    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "nemotron3-nano-30b.json")
    with open(path) as fh:
        mcfg = dict(json.load(fh)["transformer_config"], vocab_size=8192,
                    layer_pattern=pattern, num_layers=len(pattern))
    cfg = TransformerConfig(**mcfg, compute_dtype=jnp.bfloat16)
    model = TransformerLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip), shapes)
    return cfg, model, params


def test_nemotron_decode_round_compiles_for_v5e(one_chip, monkeypatch):
    """One layer of each kind of the decode round as
    ``nemotron3-nano.reason-closed-64`` runs it: 64 slots of 4096, pages of
    16. The routed products lower to two ``grouped_matmul`` custom calls
    (``ops/grouped_matmul.py``) over 384 = 64 x 6 sorted pairs, the first
    over ``moe_up`` as it lies ((width, d_model): no relayout of 1.3 GB of
    weights a layer), attention to the paged kernel at 16 query rows
    a kv head, nothing is dense over all 128 experts, and the recurrent
    state (64 x 64 x 64 x 128 float32, 134 MB) is an operand that the
    program's result aliases: updated where it lies, once in and once out."""
    import re

    from distributed_tensorflow_tpu.models.decoding import decode_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, model, params = _nemotron_layers(one_chip, "ME*")
    slots, ps, pps = 64, 16, 256

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = [{"ssm": arg((slots, 64, 64, 128), jnp.float32),
             "conv": arg((slots, 3, 6144), jnp.bfloat16)}, {},
            {"k": arg((slots * pps + 1, 2, ps, 128), jnp.bfloat16),
             "v": arg((slots * pps + 1, 2, ps, 128), jnp.bfloat16)}]

    def step(params, pool, tables, active, lengths, tok):
        dest = tables[jnp.arange(slots), lengths // ps]
        cache = {"layers": pool, "len": lengths, "pages": tables,
                 "write_page": jnp.where(active, dest, 0),
                 "attend": jnp.where(active, lengths + 1, 0),
                 "n_real": active.astype(jnp.int32),
                 "route_mask": active[:, None]}
        cache, logits = decode_step(model, params, cache, tok[:, None])
        return cache["layers"], logits.argmax(-1), cache["moe_counts"]

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, arg((slots, pps), jnp.int32), arg((slots,), jnp.bool_),
        arg((slots,), jnp.int32), arg((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [l.split(" custom-call(")[0].strip() for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    products = [c for c in calls if c.startswith("%grouped_matmul")]
    assert len(products) == 2 and all("f32[384," in c for c in products)
    assert sum("bf16[64,2,16,128]" in c for c in calls) == 1  # paged kernel
    assert "[64,128,1856]" not in text and "[384,128," not in text
    assert not re.search(r"bf16\[128,(1856,2688|2688,1856)\]\S* copy\(",
                         text)
    # The state leaf goes in and comes out in place: the donated operands
    # (ssm, conv, k, v) are aliased to the results, ONE fusion takes the
    # state (it gives the new state and the read-out together), and no copy
    # of it is made.
    alias = text[text.index("input_output_alias"):].split("\n")[0]
    assert alias.count("may-alias") + alias.count("must-alias") >= 4
    state = "f32[64,64,64,128]"
    (param,) = re.findall(r"(%\S+) = " + re.escape(state) + r"\S* parameter",
                          text[text.index("ENTRY"):])
    users = [l for l in text[text.index("ENTRY"):].splitlines()
             if param + "," in l or param + ")" in l]
    assert len(users) == 1 and " fusion(" in users[0], users
    assert not re.search(re.escape(state) + r"\S* copy\(", text)


def test_nemotron_prefill_chunk_costs_its_pairs_and_scans_in_blocks(one_chip):
    """A 1024-wide prefill chunk of one Mamba and one expert layer: the
    compiler's own count of the program's FLOPs holds the experts at the
    chunk's 6,144 pairs through ONE expert each (123 GFLOP), not all 128
    experts (2.6 TFLOP); the scan is the SSD form, whose only loop is the one
    over the chunk's 8 blocks; and the head is formed for one row."""
    import re

    cfg, model, params = _nemotron_layers(one_chip, "ME")
    width = 1024

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(params, ssm, conv, tokens, n_real, start):
        cache = {"layers": [{"ssm": ssm[None], "conv": conv[None]}, {}],
                 "len": start, "n_real": n_real[None],
                 "route_mask": (jnp.arange(width) < n_real)[None]}
        logits, cache = model.apply(
            {"params": params}, tokens, cache=cache,
            logit_rows=(n_real - 1)[None])
        return logits, cache["layers"][0], cache["moe_counts"]

    compiled = jax.jit(chunk).lower(
        params, arg((64, 64, 128), jnp.float32), arg((3, 6144), jnp.bfloat16),
        arg((1, width), jnp.int32), arg((), jnp.int32),
        arg((), jnp.int32)).compile()
    flops = compiled.cost_analysis()["flops"]
    pairs = 2 * 2 * 2688 * 1856 * 6 * width  # 6,144 rows x one expert
    assert pairs < flops < 4 * pairs, flops
    text = compiled.as_text()
    trips = [int(n) for n in re.findall(
        r'known_trip_count[^\d]*(\d+)', text)]
    assert all(n <= 128 for n in trips), trips  # never one trip a position
    assert f"f32[1,1,{cfg.vocab_size}]" in text
    assert f"[1,{width},{cfg.vocab_size}]" not in text


def _dsv2_layers(one_chip, layers=2):
    """DeepSeek-V2-Lite at the published widths (benchmarks/configs), cut to
    ``layers`` (the dense one and expert ones behind it) and an 8,192-row
    vocabulary so that it compiles in seconds: the model, its parameters as
    shapes on the described chip."""
    import json

    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "deepseek-v2-lite.json")
    with open(path) as fh:
        mcfg = dict(json.load(fh)["transformer_config"], vocab_size=8192,
                    num_layers=layers)
    cfg = TransformerConfig(**mcfg, compute_dtype=jnp.bfloat16)
    model = TransformerLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip), shapes)
    return cfg, model, params


def test_dsv2_decode_round_compiles_for_v5e(one_chip, monkeypatch):
    """The dense layer and one expert layer of the decode round as
    ``dsv2-lite.docs-closed`` runs it: 32 slots of 32768, pages of 16 latent
    rows of 640 lanes. Each layer's attention is ONE ``mla_decode_attention``
    custom call over the leaf as it lies (16 absorbed query rows of a slot
    against its one row a position, no copy of the pool, no expanded K or
    V), its row write ONE ``paged_row_write`` of that one leaf, the routed
    products two ``grouped_matmul`` calls over 192 = 32 x 6 pairs (padded to
    the kernel's 256-row tile)."""
    from distributed_tensorflow_tpu.models.decoding import decode_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, model, params = _dsv2_layers(one_chip)
    slots, ps, pps = 32, 16, 2048

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    leaf = (slots * pps + 1, 1, ps, 640)
    pool = [{"lat": arg(leaf, jnp.bfloat16)} for _ in range(2)]

    def step(params, pool, tables, active, lengths, tok):
        dest = tables[jnp.arange(slots), lengths // ps]
        cache = {"layers": pool, "len": lengths, "pages": tables,
                 "write_page": jnp.where(active, dest, 0),
                 "attend": jnp.where(active, lengths + 1, 0),
                 "route_mask": active[:, None]}
        cache, logits = decode_step(model, params, cache, tok[:, None])
        return cache["layers"], logits.argmax(-1), cache["moe_counts"]

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, arg((slots, pps), jnp.int32), arg((slots,), jnp.bool_),
        arg((slots,), jnp.int32), arg((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [l.split(" custom-call(")[0].strip() for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    assert sum(c.startswith("%mla_decode_attention") for c in calls) == 2
    assert sum("bf16[32,1,16,640]" in c for c in calls) == 2
    assert sum(c.startswith("%paged_row_write") for c in calls) == 2
    products = [c for c in calls if c.startswith("%grouped_matmul")]
    assert len(products) == 2 and all("f32[256," in c for c in products)
    assert "[32,32768," not in text  # no slot's row gathered or expanded
    assert not [l for l in text.splitlines()
                if "[65537,1,16,640]" in l and " copy(" in l]
    alias = text[text.index("input_output_alias"):].split("\n")[0]
    assert alias.count("may-alias") + alias.count("must-alias") >= 2


@pytest.mark.parametrize("width,form", [(1024, "expanded"), (128, "absorbed")])
def test_dsv2_prefill_chunk_compiles_in_the_form_of_its_width(
        one_chip, monkeypatch, width, form):
    """A prefill chunk of the dense and one expert layer over a gathered
    row of 32768 latent positions, through a chunk kernel in the form of
    its width (``models/transformer.mla_prefill_form``): a 1024-wide chunk
    through ``mla_chunk_attention``, which makes every head's K and V
    inside the kernel (no K or V of the whole row in HBM), a 128-wide tail
    with its 16 queries folded into the latent over the rows as they lie
    (``prefill_chunk_attention``, one kv head of 640 lanes). No score
    matrix over the whole row; the head is formed for one row; the program
    fits the chip beside the cell's 12.4 GB."""
    from distributed_tensorflow_tpu.models.transformer import mla_prefill_form

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, model, params = _dsv2_layers(one_chip)
    assert mla_prefill_form(cfg, width, 32768) == form

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(params, lat, tokens, n_real, start):
        cache = {"layers": [{"lat": lat[0][None]}, {"lat": lat[1][None]}],
                 "len": start, "route_mask": (jnp.arange(width) < n_real)[None]}
        logits, cache = model.apply(
            {"params": params}, tokens, cache=cache,
            logit_rows=(n_real - 1)[None])
        return logits, [l["lat"] for l in cache["layers"]]

    lat = [arg((1, 32768, 640), jnp.bfloat16) for _ in range(2)]
    compiled = jax.jit(chunk).lower(
        params, lat, arg((1, width), jnp.int32), arg((), jnp.int32),
        arg((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [l.split(" custom-call(")[0].strip() for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    if form == "expanded":
        attn = [c for c in calls if c.startswith("%mla_chunk_attention")]
        want = "bf16[8,2,%d,128]" % width  # two heads a cell
        assert not [c for c in calls
                    if c.startswith("%prefill_chunk_attention")]
        # No K or V of the whole row, expanded or broadcast, in HBM.
        assert "[16,32768," not in text and "[1,16,32768," not in text
    else:
        attn = [c for c in calls if c.startswith("%prefill_chunk_attention")]
        want = "bf16[1,16,%d,640]" % width
    assert len(attn) == 2 and all(want in c for c in attn), attn
    assert "[1,16,%d,32768]" % width not in text
    assert f"f32[1,1,{cfg.vocab_size}]" in text
    mem = compiled.memory_analysis()
    # Measured: 67,011,072 B expanded (694,298,112 with K and V of the
    # whole row made by XLA), 6,418,944 absorbed.
    assert mem.temp_size_in_bytes < 1e8, mem.temp_size_in_bytes
