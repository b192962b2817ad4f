"""The kernels and one whole served program, handed to the TPU compiler
for a DESCRIBED v5e:2x2 (no chip attached; on-chip-measurement guide §2).

Interpret-mode parity says nothing about what Mosaic accepts: before PR 21
every kernel here passed its interpret tests and was refused by the chip's
compiler at served shapes (block tiling, scoped VMEM).  These compiles keep
that from coming back, at no chip time.

The topology is described inside a module-scoped fixture — never at
import, in a skipif or in parametrize arguments — because only one process
may load the TPU library and every xdist worker imports every test file.
All of these tests live in this one file for the same reason.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

HEADS, HEAD_DIM, HIDDEN = 12, 64, 768  # mmBERT-32K / ModernBERT-base


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compilation_cache():
    """A compile for a described device can be written to the persistent
    cache but not read back without a chip: keep it out of the way."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def compile_for(sharding, fn, *shapes):
    """Lower + compile ``fn`` for the described chip; returns the
    compiled executable (raises what the chip's compiler would raise)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def assert_each_pair_moves_once_each_way(compiled, tokens, k, hidden,
                                         temp_before):
    """The expert layer's glue at a prefill row's shape (PR 33): no
    float32 array of ``pairs`` rows, no ``[T, k, H]`` array (k on a tiled
    axis is a physical copy; the combine reads ``[k, T, H]``), and
    temporaries under three quarters of what the float32 copies took."""
    text = compiled.as_text()
    assert f"f32[{tokens * k},{hidden}]" not in text
    assert f"[{tokens},{k},{hidden}]" not in text
    assert f"bf16[{k},{tokens},{hidden}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.75 * temp_before


V5E_BYTES = 16_909_336_064  # a v5e's memory_stats()["bytes_limit"]


def lfm2_param_shapes(cfg, shape):
    """The ``lfm2_moe`` parameter tree of ``cfg`` as shapes."""
    H, I, E, V, W = (cfg.hidden_size, cfg.moe_intermediate_size,
                     cfg.num_experts, cfg.vocab_size, cfg.intermediate_size)
    kv = cfg.num_key_value_heads * cfg.head_dim
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        p = {"norm1": shape((H,)), "norm2": shape((H,))}
        if kind == "conv":
            p.update(in_proj=shape((H, 3 * H)), conv_w=shape((3, H)),
                     out_proj=shape((H, H)))
        else:
            p.update(q_proj=shape((H, H)), k_proj=shape((H, kv)),
                     v_proj=shape((H, kv)), o_proj=shape((H, H)),
                     q_norm=shape((cfg.head_dim,)),
                     k_norm=shape((cfg.head_dim,)))
        if cfg.is_sparse(i):
            p.update(router=shape((H, E)),
                     expert_bias=shape((E,), jnp.float32),
                     gate_up=shape((E, H, 2 * I)), down=shape((E, I, H)))
        else:
            p.update(gate_up=shape((H, 2 * W)), down=shape((W, H)))
        layers.append(p)
    return {"embed": shape((V, H)), "norm": shape((H,)), "layers": layers}


def dots3_param_shapes(cfg, shape):
    """The ``dots3_note`` parameter tree of ``cfg`` as shapes."""
    H, I, W = cfg.hidden_size, cfg.moe_intermediate_size, \
        cfg.intermediate_size
    E, held, V = cfg.n_routed_experts, cfg.held[1], cfg.vocab[1]
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        g = cfg.geometry(kind)
        p = {"norm1": shape((H,)), "norm2": shape((H,)),
             "q_a": shape((H, g.r_q)), "q_a_norm": shape((g.r_q,)),
             "q_b": shape((g.r_q, g.heads * (g.nope + g.rope))),
             "kv_a": shape((H, g.r_kv + g.rope)),
             "kv_a_norm": shape((g.r_kv,)),
             "kv_b": shape((g.r_kv, g.heads * (g.nope + g.v))),
             "o_proj": shape((g.heads * g.v, H)),
             "gate_proj": shape((H, g.heads))}
        if kind == "full_attention":
            d = cfg.index_head_dim
            p.update(index_q=shape((g.r_q, cfg.index_n_heads * d)),
                     index_k=shape((H, d)), index_k_norm=shape((d,)),
                     index_k_bias=shape((d,)),
                     index_w=shape((H, cfg.index_n_heads)))
        if cfg.is_sparse(i):
            p.update(router=shape((H, E)),
                     expert_bias=shape((E,), jnp.float32),
                     gate_up=shape((held, H, 2 * I)),
                     down=shape((held, I, H)),
                     shared={"gate_up": shape((H, 2 * I)),
                             "down": shape((I, H))})
        else:
            p.update(gate_up=shape((H, 2 * W)), down=shape((W, H)))
        layers.append(p)
    return {"embed": shape((V, H)), "norm": shape((H,)),
            "lm_head": shape((V, H)), "layers": layers}


def joyai_param_shapes(cfg, shape):
    """The ``joyai_llm_flash`` parameter tree of ``cfg`` as shapes."""
    H, I, W = cfg.hidden_size, cfg.moe_intermediate_size, \
        cfg.intermediate_size
    E, held, V = cfg.n_routed_experts, cfg.held[1], cfg.vocab_size
    g = cfg.geometry

    def block(i):
        p = {"norm1": shape((H,)), "norm2": shape((H,)),
             "q_a": shape((H, g.r_q)), "q_a_norm": shape((g.r_q,)),
             "q_b": shape((g.r_q, g.heads * (g.nope + g.rope))),
             "kv_a": shape((H, g.r_kv + g.rope)),
             "kv_a_norm": shape((g.r_kv,)),
             "kv_b": shape((g.r_kv, g.heads * (g.nope + g.v))),
             "o_proj": shape((g.heads * g.v, H))}
        if cfg.is_sparse(i):
            p.update(router=shape((H, E)),
                     expert_bias=shape((E,), jnp.float32),
                     gate_up=shape((held, H, 2 * I)),
                     down=shape((held, I, H)),
                     shared={"gate_up": shape((H, 2 * I)),
                             "down": shape((I, H))})
        else:
            p.update(gate_up=shape((H, 2 * W)), down=shape((W, H)))
        return p

    n = cfg.num_hidden_layers
    return {"embed": shape((V, H)), "norm": shape((H,)),
            "lm_head": shape((V, H)), "layers": [block(i) for i in range(n)],
            "mtp": {"enorm": shape((H,)), "hnorm": shape((H,)),
                    "eh_proj": shape((2 * H, H)), "norm": shape((H,)),
                    "block": block(n)}}


def laguna_param_shapes(cfg, shape):
    """The ``laguna`` parameter tree of ``cfg`` as shapes."""
    H, I, W = cfg.hidden_size, cfg.moe_intermediate_size, \
        cfg.intermediate_size
    Is, D, nkv = (cfg.shared_expert_intermediate_size, cfg.head_dim,
                  cfg.num_key_value_heads)
    E, held, V = cfg.num_experts, cfg.held[1], cfg.vocab[1]
    layers = []
    for i, nh in enumerate(cfg.num_attention_heads_per_layer):
        p = {"norm1": shape((H,)), "norm2": shape((H,)),
             "q_norm": shape((D,)), "k_norm": shape((D,)),
             "q_proj": shape((H, nh * D)), "k_proj": shape((H, nkv * D)),
             "v_proj": shape((H, nkv * D)), "o_proj": shape((nh * D, H)),
             "gate_proj": shape((H, nh))}
        if cfg.is_sparse(i):
            p.update(router=shape((H, E)), gate_up=shape((held, H, 2 * I)),
                     down=shape((held, I, H)),
                     shared={"gate_up": shape((H, 2 * Is)),
                             "down": shape((Is, H))},
                     shared_gate=shape((H, 1)))
        else:
            p.update(gate_up=shape((H, 2 * W)), down=shape((W, H)))
        layers.append(p)
    return {"embed": shape((V, H)), "norm": shape((H,)),
            "lm_head": shape((V, H)), "layers": layers}


def on_the_described_chip(monkeypatch):
    """Steer the programs' platform reads to the described v5e (the test's
    backend is the CPU): the megablox kernel, the flash kernel compiled
    and not interpreted, and the device's memory for ``rows_per_group``."""
    from semantic_router_tpu.models import experts, mapped_prefill
    from semantic_router_tpu.ops import flash_attention as fa

    monkeypatch.setattr(experts, "_on_cpu", lambda: False)
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    monkeypatch.setattr(
        fa, "flash_attention_pallas",
        functools.partial(fa.flash_attention_pallas, interpret=False))
    monkeypatch.setattr(mapped_prefill, "device_bytes", lambda: V5E_BYTES)


def cell_model(name):
    """``chipbench/configs/<name>/model.json``: a cell's model numbers."""
    import json
    import os

    import chipbench

    with open(os.path.join(os.path.dirname(chipbench.__file__), "configs",
                           name, "model.json")) as f:
        return json.load(f)


def loop_of_a_generation(gen, params, cache, state, rows, M, shape,
                         steps=31):
    """The generator's decode loop compiled for the described chip at the
    cell's 32 tokens: ``(compiled, eval_shape's outs)``."""
    vec, scalar = shape((rows,), jnp.int32), shape((), jnp.int32)
    loop = gen._loop_fn((rows, 1 + gen.drafts, M, steps))
    args = (params, cache, state, vec, scalar, shape((rows,), jnp.bool_),
            shape((1,), jnp.int32), scalar)
    return loop.lower(*args).compile(), jax.eval_shape(loop, *args)


def assert_the_loop_writes_its_cache_in_place(compiled, cache, reports):
    """Every leaf of the donated ``cache`` is aliased from argument to
    result; the decode steps are ONE ``while`` (the one that carries the
    ``reports`` buffer, a step's body once), and its body holds no copy of
    a cache-shaped array: a step inside the loop writes the cache where
    it lies, as a step that was a program did.  What the body may hold is
    memory-space assignment's work — the compiler keeps a carried leaf
    that fits (a layer's latents, 76 MB) in fast memory across the steps
    and moves it with ``copy-start`` / ``slice-start`` pairs that have
    ``S(1)`` on one side: asynchronous, beside the step's compute, and no
    second home in HBM."""
    import re

    text = compiled.as_text()
    leaves = jax.tree_util.tree_leaves(cache)
    aliases = re.search(r"input_output_alias=\{(.*?may-alias\)) \}", text)
    assert aliases.group(1).count("-alias)") == len(leaves)
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    loops = [line for line in text.splitlines()
             if " while(" in line and reports in line.split(" while(")[0]]
    assert len(loops) == 1, len(loops)
    body = re.search(r"body=%?([\w.\-]+)", loops[0]).group(1)
    start = text.index("\n%" + body + " (")
    lines = text[start:text.index("\n}\n", start)].splitlines()
    assert len(lines) > 100  # a whole step
    names = {"bfloat16": "bf16", "float32": "f32", "int32": "s32"}
    for leaf in leaves:
        if int(np.prod(leaf.shape)) * leaf.dtype.itemsize < 2**20:
            continue  # lengths, a conv state of 64 KB: fast memory's own
        held = "%s[%s]" % (names[leaf.dtype.name],
                           ",".join(str(d) for d in leaf.shape))
        copies = [line for line in lines if re.search(
            r"= \(?" + re.escape(held) + r"[^=]* copy\(", line)]
        assert copies == [], copies[:2]
        moved = [line for line in lines if re.search(
            r"= \(" + re.escape(held) + r"[^=]* copy-start\(", line)]
        for line in moved:  # between memory spaces, not within HBM
            sides = re.findall(re.escape(held) + r"\{[^}]*\}",
                               line.split(" copy-start(")[0])[:2]
            assert sum("S(1)" in side for side in sides) == 1, line[:300]
    return text


# padded batches 1..max_batch_size at the short buckets; the batches that
# fit one chip's HBM at the long ones (32 x 32768 does not: see below)
FLASH_SHAPES = [(b, s) for s in (128, 512) for b in (1, 2, 4, 8, 16, 32)] \
    + [(1, 2048), (8, 2048), (32, 2048), (1, 8192), (4, 8192), (32, 8192),
       (1, 32768), (2, 32768)]


class TestFlashCompilesForV5e:
    @pytest.mark.parametrize("batch,seq", FLASH_SHAPES)
    def test_served_shape(self, one_chip, batch, seq):
        """Every (bucket, padded batch) — including B=8 x S=128/512 (the
        [B, Sp] key-bias block the compiler refused) and B=1 x S=32768
        (whole-sequence K/V blocks: 32 MiB of a 16 MiB scoped VMEM)."""
        from semantic_router_tpu.ops.flash_attention import (
            flash_attention_pallas,
        )

        for dtype in (jnp.float32, jnp.bfloat16):
            for window in (0, 128):
                qkv = ((batch, HEADS, seq, HEAD_DIM), dtype)
                compiled = compile_for(
                    one_chip,
                    functools.partial(flash_attention_pallas,
                                      window=window, interpret=False),
                    qkv, qkv, qkv, ((batch, seq), jnp.int32))
                assert "tpu_custom_call" in compiled.as_text()

    def test_full_batch_at_32k_exceeds_hbm_not_the_kernel(self, one_chip):
        """max_batch_size 32 x bucket 32768 is a batch the default config
        can form and one chip cannot hold: q/k/v alone need 24 GB.  The
        refusal is HBM capacity — the batcher's to avoid — not tiling."""
        from semantic_router_tpu.ops.flash_attention import (
            flash_attention_pallas,
        )

        qkv = ((32, HEADS, 32768, HEAD_DIM), jnp.float32)
        with pytest.raises(Exception, match="(?i)hbm"):
            compile_for(one_chip,
                        functools.partial(flash_attention_pallas,
                                          interpret=False),
                        qkv, qkv, qkv, ((32, 32768), jnp.int32))

    def test_causal_at_the_global_blocks(self, one_chip):
        """Causal callers take the global pair; the causal mask's two
        [block_q, block_k] iotas are the most VMEM any call needs."""
        from semantic_router_tpu.ops.flash_attention import (
            flash_attention_pallas,
        )

        for dtype in (jnp.float32, jnp.bfloat16):
            qkv = ((1, HEADS, 8192, HEAD_DIM), dtype)
            compiled = compile_for(
                one_chip,
                functools.partial(flash_attention_pallas, causal=True,
                                  interpret=False),
                qkv, qkv, qkv, ((1, 8192), jnp.int32))
            assert "tpu_custom_call" in compiled.as_text()

    @pytest.mark.parametrize("seq", [512, 8192])
    def test_sharded_over_dp_tp_mesh(self, topo, seq):
        """Under engine.mesh GSPMD refuses to partition a Mosaic kernel;
        flash_attention_sharded shard_maps it over (dp, tp); a shard's
        sequence is the whole one, so its blocks are the same."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from semantic_router_tpu.ops.flash_attention import (
            flash_attention_sharded,
        )

        mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
        qkv = jax.ShapeDtypeStruct(
            (4, HEADS, seq, HEAD_DIM), jnp.float32,
            sharding=NamedSharding(mesh, P("dp", "tp", None, None)))
        mask = jax.ShapeDtypeStruct(
            (4, seq), jnp.int32, sharding=NamedSharding(mesh, P("dp", None)))
        compiled = jax.jit(functools.partial(
            flash_attention_sharded, mesh=mesh, window=128,
            interpret=False)).lower(qkv, qkv, qkv, mask).compile()
        assert "tpu_custom_call" in compiled.as_text()


class TestEpilogueCompilesForV5e:
    @pytest.mark.parametrize("tasks", [6, 1])
    @pytest.mark.parametrize("rows", [1, 8, 32, 256, 300])
    def test_served_shape(self, one_chip, tasks, rows):
        """T=6 was refused: (1, H) bias blocks of [T, H] and (br, 1, H)
        delta/out blocks of [rows, T, H]."""
        from semantic_router_tpu.models.modernbert import activation
        from semantic_router_tpu.ops.epilogue import head_epilogue_pallas

        act = activation("gelu")  # what every ModernBERT head serves
        x = ((rows, HIDDEN), jnp.float32)
        w = ((tasks, HIDDEN, HIDDEN), jnp.float32)
        b = ((tasks, HIDDEN), jnp.float32)
        d = ((rows, tasks, HIDDEN), jnp.float32)
        for with_bias, with_delta in ((False, False), (True, False),
                                      (False, True), (True, True)):
            shapes = [x, w] + ([b] if with_bias else []) \
                + ([d] if with_delta else [])

            def fn(x, w, *rest):
                rest = list(rest)
                bias = rest.pop(0) if with_bias else None
                delta = rest.pop(0) if with_delta else None
                return head_epilogue_pallas(x, w, bias, delta, act,
                                            interpret=False)

            compiled = compile_for(one_chip, fn, *shapes)
            assert "tpu_custom_call" in compiled.as_text()

    def test_unlowerable_activation_is_refused_loudly(self, one_chip):
        """Mosaic lowers no erf/erfc: an ad-hoc exact GELU raises the
        compiler's error instead of serving the reference under the
        kernel's name (gelu_exact itself is swapped for an in-kernel
        form)."""
        from semantic_router_tpu.ops.epilogue import head_epilogue_pallas

        with pytest.raises(NotImplementedError, match="erf"):
            compile_for(
                one_chip,
                lambda x, w: head_epilogue_pallas(
                    x, w, None, None,
                    lambda h: jax.nn.gelu(h, approximate=False),
                    interpret=False),
                ((8, HIDDEN), jnp.float32),
                ((6, HIDDEN, HIDDEN), jnp.float32))


class TestBgmvCompilesForV5e:
    @pytest.mark.parametrize("tasks", [8, 64])
    @pytest.mark.parametrize("pairs", [1, 8, 16, 256])
    def test_served_shape(self, one_chip, tasks, pairs):
        """P > 1 was refused: (1, D) blocks of [P, D].  Both matmuls of
        apply_head_bank_bgmv: the head dense and the label projection."""
        from semantic_router_tpu.ops.bgmv import bgmv_pallas

        for out_width in (HIDDEN, 16):
            compiled = compile_for(
                one_chip,
                functools.partial(bgmv_pallas, interpret=False),
                ((pairs, HIDDEN), jnp.float32),
                ((tasks, HIDDEN, out_width), jnp.float32),
                ((pairs,), jnp.int32))
            assert "tpu_custom_call" in compiled.as_text()


class TestWholeFusedStepCompilesForV5e:
    # bucket 8192 is the benchmark's: the kernel's largest blocks (1024 x
    # 1024) inside a whole program, where XLA holds scoped VMEM of its own
    @pytest.mark.parametrize("seq,gib_lo,gib_hi",
                             [(512, 0.5, 2.0), (8192, 2.5, 5.0)])
    def test_engine_program_b8(self, one_chip, monkeypatch, seq, gib_lo,
                               gib_hi):
        """The engine's own fused seq program (trunk + head bank) at the
        published widths, B=8 x S=512 and S=8192, with the flash kernel
        in all 22 layers — and its memory, so a program that cannot fit
        16 GB is known before a chip call."""
        import semantic_router_tpu.ops.flash_attention as fa
        from semantic_router_tpu.config.schema import InferenceEngineConfig
        from semantic_router_tpu.engine.classify import InferenceEngine
        from semantic_router_tpu.models.modernbert import (
            ModernBertConfig,
            ModernBertForSequenceClassification,
        )
        from semantic_router_tpu.utils.tokenization import HashTokenizer

        # steer the dispatcher's two platform reads to the described chip
        # (jax.default_backend() is "cpu" here): in the test, not through
        # an option of the program
        monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
        monkeypatch.setattr(
            fa, "flash_attention_pallas",
            functools.partial(fa.flash_attention_pallas, interpret=False))

        engine = InferenceEngine(InferenceEngineConfig())
        try:
            trunk = None
            for name, n_labels in (("intent", 14), ("jailbreak", 2)):
                module = ModernBertForSequenceClassification(
                    ModernBertConfig(
                        num_labels=n_labels,
                        max_position_embeddings=32768,
                        rope_scaling={
                            "rope_type": "yarn", "factor": 4.0,
                            "original_max_position_embeddings": 8192},
                        attention_impl="flash"))
                shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                                        jnp.ones((1, 8), jnp.int32))
                params = {"params": dict(jax.tree_util.tree_map(
                    lambda s: np.zeros(s.shape, s.dtype),
                    shapes)["params"])}
                if trunk is None:
                    trunk = params["params"]["model"]
                params["params"]["model"] = trunk
                engine.register_task(
                    name, "sequence", module, params,
                    HashTokenizer(vocab_size=50368),
                    [str(i) for i in range(n_labels)])
            (g,) = engine._groups_by_gid.values()
            assert g.members == ["intent", "jailbreak"]

            def abstract(tree):
                return jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        np.shape(a), a.dtype, sharding=one_chip), tree)

            ids = jax.ShapeDtypeStruct((8, seq), jnp.int32,
                                       sharding=one_chip)
            compiled = g.fns["seq"].lower(
                abstract(g.fns["trunk_params"]),
                abstract(g.demux["bank"]), ids, ids).compile()
        finally:
            engine.shutdown()
        assert compiled.as_text().count("tpu_custom_call") == 22
        mem = compiled.memory_analysis()
        total = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            + mem.output_size_in_bytes
        # ~0.57 GiB of arguments + the temporaries
        assert gib_lo * 2**30 < total < gib_hi * 2**30


class TestBlockDiffusionGuardCompilesForV5e:
    """The sdar_moe guard's kernels at the published widths (hidden 2048,
    32 x 128 heads over 4 k/v heads, 128 experts of width 768)."""

    @pytest.mark.parametrize("rows", [1, 16])
    def test_block_causal_prefill_attention(self, one_chip, rows):
        """Head size 128, bfloat16, the prompt bucket as ONE 512 x 512
        block, the block-causal mask (groups of 4)."""
        from semantic_router_tpu.ops.flash_attention import (
            flash_attention_pallas,
        )

        qkv = ((rows, 32, 512, 128), jnp.bfloat16)
        compiled = compile_for(
            one_chip,
            functools.partial(flash_attention_pallas, causal=True,
                              causal_block=4, interpret=False),
            qkv, qkv, qkv, ((rows, 512), jnp.int32))
        assert "tpu_custom_call" in compiled.as_text()

    @pytest.mark.parametrize("tokens", [4, 8, 64, 128, 512, 8192])
    def test_expert_layer_grouped_matmul(self, one_chip, monkeypatch, tokens):
        """The megablox kernel under ``moe``'s tiling rule: a block forward
        of 1 and of 16 rows, of one block and of two (the forward that
        commits the block before), a prefill of 1 and of 16 rows."""
        from semantic_router_tpu.models import experts
        from semantic_router_tpu.models import sdar_moe as M

        monkeypatch.setattr(experts, "_on_cpu", lambda: False)
        cfg = M.SdarMoeConfig(num_hidden_layers=1)
        H, I, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        bf = jnp.bfloat16

        def layer(router, gate_up, down, x, valid):
            p = {"router": router, "gate_up": gate_up, "down": down}
            return M.moe(cfg, p, x, valid)

        compiled = compile_for(
            one_chip, layer, ((H, E), bf), ((E, H, 2 * I), bf),
            ((E, I, H), bf), ((tokens, H), bf), ((tokens,), jnp.bool_))
        assert compiled.as_text().count("tpu_custom_call") >= 2
        if tokens == 8192:  # 65,536 pairs: before PR 33, 1.08 GB
            assert_each_pair_moves_once_each_way(
                compiled, tokens, cfg.num_experts_per_tok, H, 1_075_564_544)

    @staticmethod
    def block_programs(one_chip, monkeypatch, rows):
        """The generator's two block programs at the cell's shapes (bucket
        512, blocks of 4, one layer of the published widths) with their
        arguments as shapes on the described chip, and the cache's."""
        from semantic_router_tpu.models import experts
        from semantic_router_tpu.models import sdar_moe as M
        from semantic_router_tpu.models.generate import (
            BlockDiffusionGenerator,
        )

        monkeypatch.setattr(experts, "_on_cpu", lambda: False)
        cfg = M.SdarMoeConfig(num_hidden_layers=1)
        H, I, E, V = (cfg.hidden_size, cfg.moe_intermediate_size,
                      cfg.num_experts, cfg.vocab_size)
        bf, L = jnp.bfloat16, 4

        def shape(dims, dtype=bf):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

        layer = {"norm1": shape((H,)), "norm2": shape((H,)),
                 "q_proj": shape((H, 4096)), "k_proj": shape((H, 512)),
                 "v_proj": shape((H, 512)), "o_proj": shape((4096, H)),
                 "q_norm": shape((128,)), "k_norm": shape((128,)),
                 "router": shape((H, E)), "gate_up": shape((E, H, 2 * I)),
                 "down": shape((E, I, H))}
        params = {"embed": shape((V, H)), "layers": [layer],
                  "norm": shape((H,)), "lm_head": shape((H, V))}
        gen = BlockDiffusionGenerator(cfg, None, None, mask_token_id=151669,
                                      block_length=L)
        cache_len = gen.cache_len(512, gen.gen_length)
        cache = shape((rows, cfg.num_key_value_heads, cache_len,
                       cfg.head_dim))
        block = shape((rows, L), jnp.int32)
        base, valid = shape((rows,), jnp.int32), shape((rows,), bool)
        step = shape((), jnp.int32)
        outs = tuple(shape(a.shape, a.dtype) for a in gen.buffers(rows))
        _, denoise, commit = gen.programs(rows, 512, cache_len)
        return gen, cache, {
            "denoise": (denoise, (
                params, [(cache, cache)], block, shape((rows, L), bool),
                base, valid, step, outs)),
            "commit": (commit, (
                params, [(cache, cache)], block, base, valid, step))}

    @pytest.mark.parametrize("rows", [1, 16])
    def test_the_committing_forward_writes_the_cache_in_place(
            self, one_chip, monkeypatch, rows):
        """The generator's ``commit`` program (two blocks a row): the
        donated cache comes back as the same buffers, the head scores one
        block, and what the loop goes on from has the loop's shapes."""
        gen, cache, programs = self.block_programs(one_chip, monkeypatch,
                                                   rows)
        commit, args = programs["commit"]
        compiled = commit.lower(*args).compile()
        assert compiled.as_text().count("tpu_custom_call") >= 2
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == 2 * int(np.prod(cache.shape)) * 2
        L, k = gen.block_length, 8
        _, tokens, masked, start, outs, committed = jax.eval_shape(
            commit, *args)
        assert tokens.shape == masked.shape == (rows, L)
        assert start.shape == (rows,) and committed.shape == (1, rows, L, k)
        loop_args = programs["denoise"][1]
        assert [(o.shape, o.dtype) for o in outs] == \
            [(a.shape, a.dtype) for a in loop_args[-1]]
        assert outs[0].shape == (gen.denoising_steps, rows, L,
                                 4 + 2 * gen.top_logits)

    def test_a_blocks_forwards_are_one_loop_that_copies_no_cache(
            self, one_chip, monkeypatch):
        """16 rows, the cell's shape: the forwards of a block are ONE
        ``while`` of the compiled ``denoise`` program (the one that carries
        the reports' buffer), the cache is not among what it carries forward
        changed, and its body holds no copy of a cache-shaped array — a
        forward inside the loop reads the cache where it lies, as a forward
        that was a program did."""
        import re

        gen, cache, programs = self.block_programs(one_chip, monkeypatch, 16)
        fn, args = programs["denoise"]
        text = fn.lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        reports = "f32[%d,16,4,%d]" % (gen.denoising_steps,
                                       4 + 2 * gen.top_logits)
        loops = [line for line in text.splitlines()
                 if " while(" in line and reports in line.split(" while(")[0]]
        assert len(loops) == 1, len(loops)
        body = re.search(r"body=%?([\w.\-]+)", loops[0]).group(1)
        start = text.index("\n%" + body + " (")
        lines = text[start:text.index("\n}\n", start)].splitlines()
        assert len(lines) > 100  # a whole forward
        held = "bf16[%s]" % ",".join(str(d) for d in cache.shape)
        copies = [line for line in lines if re.search(
            r"= \(?" + re.escape(held) + r"[^=]* copy(-start)?\(", line)]
        assert copies == [], copies[:2]
        # what the loop gives back of the cache is what went in: the body's
        # root hands on its own parameter's element, untouched
        root = next(line for line in lines if "ROOT" in line)
        handed_on = 0
        for operand in re.findall(r"%[\w.\-]+", root.split(" tuple(")[1]):
            made = next(line for line in lines
                        if line.lstrip().startswith(operand + " = "))
            if held in made.split(" = ")[1].split(" ")[0]:
                assert "get-tuple-element(" in made, made[:200]
                handed_on += 1
        assert handed_on >= 2  # K and V of the layer


class TestHybridGuardCompilesForV5e:
    """The lfm2_moe guard at the published widths (hidden 2048, 32 x 64
    heads over 8 k/v heads, 64 experts of width 1536, dense 11776,
    vocabulary 65536), bucket 8192."""

    def test_causal_prefill_attention(self, one_chip):
        """Head size 64, bfloat16, a group's two rows of 32 heads over
        8192 columns under the plain causal mask, the rows' lengths a
        scalar-prefetch operand: what a mapped prefill group runs."""
        from semantic_router_tpu.ops.flash_attention import (
            flash_attention_pallas,
        )

        qkv = ((2, 32, 8192, 64), jnp.bfloat16)
        compiled = compile_for(
            one_chip,
            lambda q, k, v, m, n: flash_attention_pallas(
                q, k, v, m, causal=True, interpret=False, lengths=n),
            qkv, qkv, qkv, ((2, 8192), jnp.int32), ((2,), jnp.int32))
        assert "tpu_custom_call" in compiled.as_text()

    @pytest.mark.parametrize("tokens", [8, 8192])
    def test_expert_layer_grouped_matmul(self, one_chip, monkeypatch, tokens):
        """The shared expert layer behind the sigmoid router, at THIS
        model's matrices ([2048, 3072] and [1536, 2048], whole-K tiles): a
        decode forward of 8 rows (32 pairs), a prefill row (32 k pairs)."""
        from semantic_router_tpu.models import experts, lfm2_moe

        monkeypatch.setattr(experts, "_on_cpu", lambda: False)
        cfg = lfm2_moe.Lfm2MoeConfig(layer_types=("conv",),
                                     num_hidden_layers=1, num_dense_layers=0)
        H, I, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        bf = jnp.bfloat16

        def layer(router, bias, gate_up, down, x, valid):
            p = {"router": router, "expert_bias": bias, "gate_up": gate_up,
                 "down": down}
            return lfm2_moe.moe(cfg, p, x, valid)

        compiled = compile_for(
            one_chip, layer, ((H, E), bf), ((E,), jnp.float32),
            ((E, H, 2 * I), bf), ((E, I, H), bf), ((tokens, H), bf),
            ((tokens,), jnp.bool_))
        assert compiled.as_text().count("tpu_custom_call") >= 2
        if tokens == 8192:  # 32,768 pairs: before PR 33, 0.54 GB
            assert_each_pair_moves_once_each_way(
                compiled, tokens, cfg.num_experts_per_tok, H, 538_391_040)

    def test_the_two_programs_of_a_generation(self, one_chip, monkeypatch):
        """The generator's prefill (8 rows mapped inside it, two a group)
        and the LOOP of its decode steps over two
        layers that hold every kind of part (attention with the dense MLP,
        then a convolution with experts): the prefill's temporaries are
        one group's and under what ``rows_per_group`` reckoned for it,
        every step of the loop writes the donated hybrid cache in place
        and leaves a small report in the loop's buffers."""
        from semantic_router_tpu.models import lfm2_moe
        from semantic_router_tpu.models.generate import GreedyGenerator

        on_the_described_chip(monkeypatch)
        cfg = lfm2_moe.Lfm2MoeConfig(
            layer_types=("full_attention", "conv"),
            num_hidden_layers=2, num_dense_layers=1)
        H = cfg.hidden_size
        rows, S, M = 8, 8192, 8256

        def shape(dims, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

        params = lfm2_param_shapes(cfg, shape)
        gen = GreedyGenerator(cfg, None, None,
                              model=lfm2_moe.CachedModel(cfg))
        args = (params, shape((rows, S), jnp.int32),
                shape((rows,), jnp.int32), shape((), jnp.int32))
        prefill = gen._prefill_fn((rows, S, M))
        compiled = prefill.lower(*args).compile()
        # one causal flash call and an expert layer's two grouped matmuls
        assert compiled.as_text().count("tpu_custom_call") >= 3
        assert gen.model.rows_per_group(params, rows, S, M) == 2
        # two rows' 65 k pairs and their dense MLP, not eight rows'
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 2 * lfm2_moe._row_bytes(cfg, S) < 1.6 * 2**30
        cache, _, report, aux = jax.eval_shape(prefill, *args)
        assert report.shape == (rows, 2 + 2 * gen.top_logits)
        assert aux["experts"].shape == (1, rows, S, 4)
        cache = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype),
                                       cache)
        sizes = lfm2_moe.CachedModel(cfg).cache_bytes(cache)
        assert sizes == {"kv": 2 * rows * 8 * M * 64 * 2,
                         "conv": rows * 2 * H * 2}
        # K and V with the columns last: the layout the loop keeps
        assert cache["kv"][0][0].shape == (rows, 8, 64, M)
        loop, (_, (reports, aux), ran) = loop_of_a_generation(
            gen, params, cache, shape((rows,), jnp.int32), rows, M, shape)
        assert reports.shape == (31, rows, 2 + 2 * gen.top_logits)
        assert aux["experts"].shape == (31, 1, rows, 4)
        assert aux["load"].shape == (31, 1, 4) and ran.shape == ()
        text = assert_the_loop_writes_its_cache_in_place(
            loop, cache, "f32[31,%d,%d]" % (rows, 2 + 2 * gen.top_logits))
        # ONE decode body a row count: an expert layer's two grouped matmuls
        assert text.count("tpu_custom_call") == 2
        # a step's temporaries (3 MB as a program of its own) and a
        # weight the compiler keeps in fast memory over the steps; with
        # the columns second to last the loop padded each to 128 and
        # copied the cache in and out: 0.27 GiB
        assert loop.memory_analysis().temp_size_in_bytes < 0.1 * 2**30


class TestSparseLatentGuardCompilesForV5e:
    """The dots3_note guard at the published widths (hidden 5120; full
    layers of 128 latent heads, q/k 192 and v 128, under a per-query
    selection; sliding layers of 64 heads, q/k 256, over 513 keys; 32 of
    256 experts of width 1536 held beside a shared one; 19,008 vocabulary
    rows), bucket 8192."""

    @pytest.mark.parametrize("kind", ["full", "window"])
    def test_prefill_attention_cores(self, one_chip, kind):
        """One row's heads over 8192 columns, bfloat16, v's head size
        apart from q/k's: causal under the int8 selection ``[S, S]``, and
        causal with the window on the Pallas path; the row's length a
        scalar-prefetch operand, as the prefill hands it."""
        from semantic_router_tpu.ops.flash_attention import (
            flash_attention_pallas,
        )

        heads, d = (128, 192) if kind == "full" else (64, 256)
        qk, v = ((1, heads, 8192, d), jnp.bfloat16), \
            ((1, heads, 8192, 128), jnp.bfloat16)
        mask, lengths = ((1, 8192), jnp.int32), ((1,), jnp.int32)
        if kind == "full":
            compiled = compile_for(
                one_chip,
                lambda q, k, v, m, n, s: flash_attention_pallas(
                    q, k, v, m, causal=True, select=s, interpret=False,
                    lengths=n),
                qk, qk, v, mask, lengths, ((1, 8192, 8192), jnp.int8))
        else:
            compiled = compile_for(
                one_chip,
                lambda q, k, v, m, n: flash_attention_pallas(
                    q, k, v, m, causal=True, window=2 * 512,
                    interpret=False, lengths=n),
                qk, qk, v, mask, lengths)
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        assert f"bf16[{heads},8192,128]" in text  # the output is v's size

    @pytest.mark.parametrize("tokens", [8, 8192])
    def test_expert_layer_beside_the_shared_expert(self, one_chip,
                                                   monkeypatch, tokens):
        """``routed_experts`` at THIS model's matrices ([5120, 3072] by the
        rule's tiles, [1536, 5120]) with 32 of 256 experts held, behind
        the sigmoid router of 256 outputs, plus the shared expert."""
        from semantic_router_tpu.models import experts, dots3_note

        monkeypatch.setattr(experts, "_on_cpu", lambda: False)
        cfg = dots3_note.Dots3NoteConfig(experts_held=(0, 32))
        H, I, E = (cfg.hidden_size, cfg.moe_intermediate_size,
                   cfg.n_routed_experts)
        bf = jnp.bfloat16

        def layer(router, bias, gate_up, down, s_gate_up, s_down, x, valid):
            p = {"router": router, "expert_bias": bias, "gate_up": gate_up,
                 "down": down,
                 "shared": {"gate_up": s_gate_up, "down": s_down}}
            return dots3_note.moe(cfg, p, x, valid)

        compiled = compile_for(
            one_chip, layer, ((H, E), bf), ((E,), jnp.float32),
            ((32, H, 2 * I), bf), ((32, I, H), bf), ((H, 2 * I), bf),
            ((I, H), bf), ((tokens, H), bf), ((tokens,), jnp.bool_))
        assert compiled.as_text().count("tpu_custom_call") >= 2

    def test_the_two_programs_of_a_generation(self, one_chip, monkeypatch):
        """The generator's prefill (8 rows mapped inside it, one a group:
        a row's activations are 84 MB) and the LOOP of its decode steps
        over two layers that hold every kind of part (a full layer with
        the dense MLP, a sliding layer with experts): the prefill's
        temporaries are one row's and under what ``rows_per_group``
        reckoned for it, every step of the loop writes the donated latent
        cache in place and leaves a small report in the loop's buffers."""
        from semantic_router_tpu.models import dots3_note
        from semantic_router_tpu.models.generate import GreedyGenerator

        on_the_described_chip(monkeypatch)
        cfg = dots3_note.Dots3NoteConfig(
            layer_types=("full_attention", "sliding_attention"),
            num_hidden_layers=2, experts_held=(0, 32),
            vocab_held=(0, 19008))
        rows, S, M = 8, 8192, 8256

        def shape(dims, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

        params = dots3_param_shapes(cfg, shape)
        gen = GreedyGenerator(cfg, None, None,
                              model=dots3_note.CachedModel(cfg))
        args = (params, shape((rows, S), jnp.int32),
                shape((rows,), jnp.int32), shape((), jnp.int32))
        prefill = gen._prefill_fn((rows, S, M))
        compiled = prefill.lower(*args).compile()
        # both cores and an expert layer's two grouped matmuls
        assert compiled.as_text().count("tpu_custom_call") >= 4
        assert gen.model.rows_per_group(params, rows, S, M) == 1
        # a row's heads, scores of one block of queries, a row's pairs
        assert compiled.memory_analysis().temp_size_in_bytes \
            < dots3_note._row_bytes(cfg, S) < 5.5 * 2**30
        cache, _, report, aux = jax.eval_shape(prefill, *args)
        assert report.shape == (rows, 2 + 2 * gen.top_logits)
        assert aux["experts"].shape == (1, rows, S, 8)
        assert aux["keys"].shape == (rows, 2)
        assert aux["selected"].shape == (1, rows, dots3_note.SELECT_SAMPLE,
                                         S // 8)
        cache = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype),
                                       cache)
        sizes = dots3_note.CachedModel(cfg).cache_bytes(cache)
        assert sizes == {"latent": rows * M * (512 + 64) * 2,
                         "index": rows * M * 128 * 2,
                         "window": rows * 513 * (1024 + 64) * 2}
        loop, (_, (reports, aux), ran) = loop_of_a_generation(
            gen, params, cache, shape((rows,), jnp.int32), rows, M, shape)
        assert reports.shape == (31, rows, 2 + 2 * gen.top_logits)
        # a step's selected bits wait in the loop's buffer: 0.26 MB a layer
        assert aux["selected"].shape == (31, 1, rows, M // 8)
        assert aux["keys"].shape == (31, rows, 2) and ran.shape == ()
        text = assert_the_loop_writes_its_cache_in_place(
            loop, cache, "f32[31,%d,%d]" % (rows, 2 + 2 * gen.top_logits))
        assert text.count("tpu_custom_call") == 2  # ONE decode body
        # a step's temporaries (0.12 GiB as a program of its own) and one
        # home in HBM for the layer's latents (76 MB), which live in fast
        # memory while the loop runs
        assert loop.memory_analysis().temp_size_in_bytes < 0.3 * 2**30


class TestSelfDraftingGuardCompilesForV5e:
    """The joyai_llm_flash guard at the published widths (hidden 2048; 32
    latent heads, q/k 192 and v 128; 128 of 256 experts of width 768 held
    beside a shared one; the whole vocabulary of 129,280; the MTP module),
    bucket 512 x 16 rows: the dense layer, one expert layer and the
    module, which is every kind of part the cell's seven blocks have."""

    def test_the_two_programs_of_a_generation(self, one_chip, monkeypatch):
        """The generator's prefill (the layers, the choice, then the
        module over the prompt) and the LOOP of its steps (two positions
        a row through the layers, the choice, the module, the advance, the
        rows' counts against their budget: ONE program whose every step
        writes both donated latent caches in place and leaves a small
        report in the loop's buffers)."""
        from semantic_router_tpu.models import joyai_llm_flash
        from semantic_router_tpu.models.generate import GreedyGenerator

        on_the_described_chip(monkeypatch)
        cfg = joyai_llm_flash.JoyaiLlmFlashConfig(
            num_hidden_layers=2, experts_held=(0, 128))
        rows, S, M = 16, 512, 576

        def shape(dims, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

        params = joyai_param_shapes(cfg, shape)
        gen = GreedyGenerator(cfg, None, None,
                              model=joyai_llm_flash.CachedModel(cfg))
        assert gen.drafts
        vec, scalar = shape((rows,), jnp.int32), shape((), jnp.int32)
        args = (params, shape((rows, S), jnp.int32), vec, scalar)
        prefill = gen._prefill_fn((rows, S, M))
        compiled = prefill.lower(*args).compile()
        # three attention cores, two expert layers' two grouped matmuls
        assert compiled.as_text().count("tpu_custom_call") >= 7
        assert compiled.memory_analysis().temp_size_in_bytes < 1.0 * 2**30
        cache, state, report, aux = jax.eval_shape(prefill, *args)
        assert "hidden" not in cache  # the prompt's h_i stay in the program
        assert [a.shape for a in state] == [(rows,), (rows,)]
        assert [a.shape for a in report] == [(rows, 2 + 2 * gen.top_logits)] * 2
        assert aux["experts"].shape == (2, rows, S, 8)
        cache = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype),
                                       cache)
        sizes = joyai_llm_flash.CachedModel(cfg).cache_bytes(cache)
        assert sizes == {"latent": 2 * rows * M * (512 + 64) * 2,
                         "draft": rows * M * (512 + 64) * 2}
        loop, (_, ((chosen, accepted, drafted), aux), ran) = \
            loop_of_a_generation(gen, params, cache, (vec, vec), rows, M,
                                 shape)
        assert chosen.shape == (31, rows, 2, 2 + 2 * gen.top_logits)
        assert accepted.shape == (31, rows) and ran.shape == ()
        assert drafted.shape == (31, rows, 2 + 2 * gen.top_logits)
        assert aux["experts"].shape == (31, 2, rows, 2, 8)
        assert aux["load"].shape == (31, 2, 4)
        text = assert_the_loop_writes_its_cache_in_place(
            loop, cache, "f32[31,%d,2,%d]" % (rows, 2 + 2 * gen.top_logits))
        # ONE body of two positions a row: the layer's and the module's
        # expert layers, two grouped matmuls each
        assert text.count("tpu_custom_call") == 4
        # a step's temporaries (45 MB as a program of its own) and the
        # transposes of q_b and kv_b, 27 MB a block, which the compiler
        # makes once before the loop and not once a step
        assert loop.memory_analysis().temp_size_in_bytes < 0.2 * 2**30


class TestLongPromptGuardsPrefillFitsAtTheRulesGroup:
    """The 8 x 8192 prefill of both long-prompt guards at their cells'
    widths and depths (``chipbench/configs/*/model.json``), at the rows a
    group ``mapped_prefill.rows_per_group`` gives on a v5e's memory."""

    @pytest.mark.parametrize("name, group", [("lfm2-24b-a2b-guard", 2),
                                             ("dots3-note-guard", 1)])
    def test_arguments_and_temporaries_fit(self, one_chip, monkeypatch, name,
                                           group):
        from semantic_router_tpu.models import dots3_note, lfm2_moe

        on_the_described_chip(monkeypatch)
        if name.startswith("lfm2"):
            module, shapes = lfm2_moe, lfm2_param_shapes
            cfg = lfm2_moe.Lfm2MoeConfig.from_hf(cell_model(name))
        else:
            module, shapes = dots3_note, dots3_param_shapes
            cfg = dots3_note.Dots3NoteConfig.from_hf(
                cell_model(name), experts_held=(0, 32),
                vocab_held=(0, 19008))
        groups = []
        real = module._prefill_groups
        monkeypatch.setattr(
            module, "_prefill_groups",
            lambda *a: groups.append(a[-1]) or real(*a))

        def shape(dims, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

        compiled = jax.jit(
            lambda p, i, n: module.prefill(cfg, p, i, n, 8256)).lower(
                shapes(cfg, shape), shape((8, 8192), jnp.int32),
                shape((8,), jnp.int32)).compile()
        assert groups == [group]
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes > 10.0e9  # the cell's weights
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 15.0 * 2**30
        assert mem.temp_size_in_bytes < group * module._row_bytes(cfg, 8192)


class TestWindowAndFullGuardCompilesForV5e:
    """The laguna guard at the published widths (hidden 3072; full layers
    of 48 query heads and sliding layers of 72 over 8 k/v heads of 128, a
    window of 512; 128 of 256 experts of width 1024 held beside a gated
    shared one; 50,176 vocabulary rows), buckets 512 and 8192 x 8 rows."""

    @pytest.mark.parametrize("kind", ["full", "window"])
    def test_prefill_attention_cores(self, one_chip, kind):
        """One row's heads of 128 over 8192 columns, bfloat16, at the
        blocks ``blocks_for`` picks from the shape (measured at D 64):
        causal and whole at 1024 x 1024, causal under the window of 512
        keys at 256 x 512 — both within the kernel's scoped memory at D
        128, so the rule needs no head width; the row's length a
        scalar-prefetch operand, as the prefill hands it."""
        from semantic_router_tpu.ops.flash_attention import (
            blocks_for,
            flash_attention_pallas,
        )

        heads, window = (48, 0) if kind == "full" else (72, 2 * 511)
        assert blocks_for(8192, window) == \
            ((1024, 1024) if kind == "full" else (256, 512))
        qkv = ((1, heads, 8192, 128), jnp.bfloat16)
        compiled = compile_for(
            one_chip,
            lambda q, k, v, m, n: flash_attention_pallas(
                q, k, v, m, causal=True, window=window, interpret=False,
                lengths=n),
            qkv, qkv, qkv, ((1, 8192), jnp.int32), ((1,), jnp.int32))
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        assert f"bf16[{heads},8192,128]" in text

    @pytest.mark.parametrize("tokens", [8, 8192])
    def test_expert_layer_beside_the_gated_shared_expert(
            self, one_chip, monkeypatch, tokens):
        """``routed_experts`` at THIS model's matrices ([3072, 2048] and
        [1024, 3072], by the rule's tiles) with 128 of 256 experts held,
        behind the softmax router of 256 outputs and its 10 a token, plus
        the shared expert under its sigmoid."""
        from semantic_router_tpu.models import experts, laguna

        monkeypatch.setattr(experts, "_on_cpu", lambda: False)
        cfg = laguna.LagunaConfig(experts_held=(0, 128))
        H, I, E = (cfg.hidden_size, cfg.moe_intermediate_size,
                   cfg.num_experts)
        bf = jnp.bfloat16

        def layer(router, gate_up, down, s_gate_up, s_down, s_gate, x,
                  valid):
            p = {"router": router, "gate_up": gate_up, "down": down,
                 "shared": {"gate_up": s_gate_up, "down": s_down},
                 "shared_gate": s_gate}
            return laguna.moe(cfg, p, x, valid)

        compiled = compile_for(
            one_chip, layer, ((H, E), bf), ((128, H, 2 * I), bf),
            ((128, I, H), bf), ((H, 2 * I), bf), ((I, H), bf), ((H, 1), bf),
            ((tokens, H), bf), ((tokens,), jnp.bool_))
        assert compiled.as_text().count("tpu_custom_call") >= 2

    @pytest.mark.parametrize("S, M, group", [(8192, 8256, 1), (512, 576, 8)],
                             ids=["bucket_8192", "bucket_512"])
    def test_the_two_programs_of_a_generation(self, one_chip, monkeypatch, S,
                                              M, group):
        """The generator's prefill (8 rows mapped inside it: one a group at
        the long bucket, all together at the short one) and the LOOP of its
        decode steps over two layers that hold every kind of part (a full
        layer with the dense MLP, a sliding layer with experts): every step
        of the loop writes BOTH kinds of donated cache in place — the whole
        K and V at the token's column, the ring at its slot — and leaves a
        small report in the loop's buffers."""
        from semantic_router_tpu.models import laguna
        from semantic_router_tpu.models.generate import GreedyGenerator

        on_the_described_chip(monkeypatch)
        full, sliding = laguna.LAYER_TYPES
        cfg = laguna.LagunaConfig.from_hf(
            dict(cell_model("laguna-s21-guard"), num_hidden_layers=2,
                 layer_types=[full, sliding],
                 mlp_layer_types=["dense", "sparse"],
                 num_attention_heads_per_layer=[48, 72],
                 gating_types=["per_head"] * 2, num_experts=256),
            experts_held=(0, 128), vocab_held=(0, 50176))
        rows = 8

        def shape(dims, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

        params = laguna_param_shapes(cfg, shape)
        gen = GreedyGenerator(cfg, None, None,
                              model=laguna.CachedModel(cfg))
        args = (params, shape((rows, S), jnp.int32),
                shape((rows,), jnp.int32), shape((), jnp.int32))
        prefill = gen._prefill_fn((rows, S, M))
        compiled = prefill.lower(*args).compile()
        # both cores and an expert layer's two grouped matmuls
        assert compiled.as_text().count("tpu_custom_call") >= 4
        assert gen.model.rows_per_group(params, rows, S, M) == group
        assert compiled.memory_analysis().temp_size_in_bytes \
            < group * laguna._row_bytes(cfg, S) < 3.0 * 2**30
        cache, _, report, aux = jax.eval_shape(prefill, *args)
        assert report.shape == (rows, 2 + 2 * gen.top_logits)
        assert aux["experts"].shape == (1, rows, S, 10)
        cache = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype),
                                       cache)
        sizes = laguna.CachedModel(cfg).cache_bytes(cache)
        assert sizes == {"full": 2 * rows * 8 * M * 128 * 2,
                         "window": 2 * rows * 8 * 512 * 128 * 2}
        loop, (_, (reports, aux), ran) = loop_of_a_generation(
            gen, params, cache, shape((rows,), jnp.int32), rows, M, shape)
        assert reports.shape == (31, rows, 2 + 2 * gen.top_logits)
        assert aux["experts"].shape == (31, 1, rows, 10)
        assert aux["load"].shape == (31, 1, 4) and ran.shape == ()
        text = assert_the_loop_writes_its_cache_in_place(
            loop, cache, "f32[31,%d,%d]" % (rows, 2 + 2 * gen.top_logits))
        assert text.count("tpu_custom_call") == 2  # ONE decode body
        assert loop.memory_analysis().temp_size_in_bytes < 0.3 * 2**30

    def test_the_cells_prefill_fits_beside_its_weights(self, one_chip,
                                                       monkeypatch):
        """The 8 x 8192 prefill at the cell's widths and depth
        (``chipbench/configs/laguna-s21-guard/model.json``: five layers,
        128 experts and 50,176 vocabulary rows held), one row a group: its
        arguments are the cell's 11.1 GB of weights, and weights,
        temporaries and the cache it returns leave room on a v5e."""
        from semantic_router_tpu.models import laguna

        on_the_described_chip(monkeypatch)
        model = cell_model("laguna-s21-guard")
        cfg = laguna.LagunaConfig.from_hf(
            dict(model, num_experts=model["published"]["num_experts"],
                 vocab_size=model["published"]["vocab_size"]),
            experts_held=tuple(model["held"]["experts"]),
            vocab_held=tuple(model["held"]["vocab"]))

        def shape(dims, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

        params = laguna_param_shapes(cfg, shape)
        model = laguna.CachedModel(cfg)
        assert model.rows_per_group(params, 8, 8192, 8256) == 1
        assert model.rows_per_group(params, 8, 512, 576) == 8
        compiled = jax.jit(
            lambda p, i, n: laguna.prefill(cfg, p, i, n, 8256)).lower(
                params, shape((8, 8192), jnp.int32),
                shape((8,), jnp.int32)).compile()
        mem = compiled.memory_analysis()
        assert 11.0e9 < mem.argument_size_in_bytes < 11.3e9
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            + mem.output_size_in_bytes < 14.5 * 2**30
        assert mem.temp_size_in_bytes < laguna._row_bytes(cfg, 8192)


def olmo_param_shapes(cfg, shape):
    """The ``olmo_hybrid`` parameter tree of ``cfg`` as shapes."""
    H, W, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    n, dv = cfg.linear_num_key_heads, cfg.linear_value_head_dim
    layers = []
    for kind in cfg.layer_types:
        p = {"attn_norm": shape((H,)), "ffn_norm": shape((H,)),
             "gate_up": shape((H, 2 * W)), "down": shape((W, H))}
        if kind == "full_attention":
            p.update(q_proj=shape((H, H)), k_proj=shape((H, H)),
                     v_proj=shape((H, H)), o_proj=shape((H, H)),
                     q_norm=shape((H,)), k_norm=shape((H,)))
        else:
            p.update(qkv=shape((H, cfg.conv_width)),
                     conv_w=shape((cfg.linear_conv_kernel_dim,
                                   cfg.conv_width)),
                     ab=shape((H, 2 * n)), gate=shape((H, n * dv)),
                     A_log=shape((n,), jnp.float32),
                     dt_bias=shape((n,), jnp.float32),
                     o_norm=shape((dv,)), o_proj=shape((n * dv, H)))
        layers.append(p)
    return {"embed": shape((V, H)), "norm": shape((H,)),
            "lm_head": shape((V, H)), "layers": layers}


class TestLinearAttentionGuardCompilesForV5e:
    """The ``olmo_hybrid`` guard at its published widths (30 heads of 96 x
    192 over a float32 state, hidden 3840, SwiGLU 11008, vocabulary
    100352): the chunked gated delta rule at the served shape, the two
    programs of a generation over one period of layers, and the cell's
    whole 12-layer prefill beside its weights."""

    def test_the_chunked_scan_at_the_served_shape(self, one_chip,
                                                  monkeypatch):
        """One row of 8192 tokens with its real length: 96 and 192 go
        through Mosaic as the arrays' own last dims (no padding to the lane
        tile in HBM), the sequential pass is ONE kernel, and what the op
        holds for every chunk at once is under a gigabyte."""
        from semantic_router_tpu.ops import gated_delta_rule as gdr

        on_the_described_chip(monkeypatch)
        B, H, S, dk, dv = 1, 30, 8192, 96, 192
        compiled = compile_for(
            one_chip,
            lambda q, k, v, g, b, n: gdr.chunk_gated_delta_rule(
                q, k, v, g, b, lengths=n),
            ((B, H, S, dk), jnp.bfloat16), ((B, H, S, dk), jnp.bfloat16),
            ((B, H, S, dv), jnp.bfloat16), ((B, H, S), jnp.float32),
            ((B, H, S), jnp.float32), ((B,), jnp.int32))
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1
        # the kernel's operands as the op hands them over: a chunk a block
        assert "f32[30,128,64,96]" in text and "f32[30,128,64,192]" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 1.0 * 2**30

    def test_the_two_programs_of_a_generation(self, one_chip, monkeypatch):
        """The generator's prefill (8 rows mapped inside it, one a group)
        and the LOOP of its decode steps over one period (three linear
        layers, one full): the prefill's temporaries are one row's and
        under what ``_row_bytes`` reckoned, every step of the loop writes
        all three kinds of donated cache in place — K and V at the token's
        row, the float32 states, the conv windows — and leaves a small
        report in the loop's buffers."""
        from semantic_router_tpu.models import olmo_hybrid
        from semantic_router_tpu.models.generate import GreedyGenerator

        on_the_described_chip(monkeypatch)
        cfg = olmo_hybrid.OlmoHybridConfig.from_hf(dict(
            cell_model("olmo-hybrid-7b-guard"), num_hidden_layers=4,
            layer_types=["linear_attention"] * 3 + ["full_attention"]))
        rows, S, M = 8, 8192, 8256

        def shape(dims, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

        params = olmo_param_shapes(cfg, shape)
        gen = GreedyGenerator(cfg, None, None,
                              model=olmo_hybrid.CachedModel(cfg))
        args = (params, shape((rows, S), jnp.int32),
                shape((rows,), jnp.int32), shape((), jnp.int32))
        prefill = gen._prefill_fn((rows, S, M))
        compiled = prefill.lower(*args).compile()
        # three scans and one causal flash call
        assert compiled.as_text().count("tpu_custom_call") == 4
        assert gen.model.rows_per_group(params, rows, S, M) == 1
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 1.1 * olmo_hybrid._row_bytes(cfg, S) < 1.7 * 2**30
        cache, _, report, aux = jax.eval_shape(prefill, *args)
        assert report.shape == (rows, 2 + 2 * gen.top_logits)
        assert aux["load"].shape == (0, 4)
        cache = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype),
                                       cache)
        sizes = olmo_hybrid.CachedModel(cfg).cache_bytes(cache)
        assert sizes == {"full": 2 * rows * 30 * M * 128 * 2,
                         "state": 3 * rows * 30 * 96 * 192 * 4,
                         "conv": 3 * rows * 3 * 11520 * 2}
        assert sum(sizes.values()) == olmo_hybrid._cache_bytes(cfg, rows, M)
        assert cache["state"][0].dtype == jnp.float32
        loop, (_, (reports, aux), ran) = loop_of_a_generation(
            gen, params, cache, shape((rows,), jnp.int32), rows, M, shape)
        assert reports.shape == (31, rows, 2 + 2 * gen.top_logits)
        assert aux["load"].shape == (31, 0, 4) and ran.shape == ()
        text = assert_the_loop_writes_its_cache_in_place(
            loop, cache, "f32[31,%d,%d]" % (rows, 2 + 2 * gen.top_logits))
        assert "tpu_custom_call" not in text  # a step is plain jnp
        assert loop.memory_analysis().temp_size_in_bytes < 0.3 * 2**30

    def test_the_cells_prefill_fits_beside_its_weights(self, one_chip,
                                                       monkeypatch):
        """The 8 x 8192 prefill at the cell's widths and depth
        (``chipbench/configs/olmo-hybrid-7b-guard/model.json``: 12
        layers), one row a group: its arguments are the cell's 6.54 GB of
        weights, and weights, temporaries and the 3.2 GB of cache it
        returns stay far under the 15.0e9 the configuration's rule allows.
        Without the barrier after each layer the scheduler cut every
        layer's conv window from its ``[S, 11520]`` input at the program's
        end and held them all (at 16 layers 4.0 GB of temporaries, not
        2.0)."""
        from semantic_router_tpu.models import olmo_hybrid

        on_the_described_chip(monkeypatch)
        cfg = olmo_hybrid.OlmoHybridConfig.from_hf(
            cell_model("olmo-hybrid-7b-guard"))

        def shape(dims, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

        params = olmo_param_shapes(cfg, shape)
        for rows in (1, 2, 4, 8):
            assert olmo_hybrid.CachedModel(cfg).rows_per_group(
                params, rows, 8192, 8256) == 1
        compiled = jax.jit(
            lambda p, i, n: olmo_hybrid.prefill(cfg, p, i, n, 8256)).lower(
                params, shape((8, 8192), jnp.int32),
                shape((8,), jnp.int32)).compile()
        mem = compiled.memory_analysis()
        assert 6.53e9 < mem.argument_size_in_bytes < 6.55e9
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            + mem.output_size_in_bytes < 12.0e9
        assert mem.temp_size_in_bytes < 1.4 * olmo_hybrid._row_bytes(
            cfg, 8192)
