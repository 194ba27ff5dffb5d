"""Rehearsal of chip_smoke.py without the chip.

The phase bodies run at LlamaConfig.tiny on the CPU, which finds wrong paths,
arguments and control flow at no chip time. The script itself has no CPU
mode: run as the driver runs it, with JAX held to the CPU, it must fail.
"""

import json
import os
import subprocess
import sys
from unittest import mock

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _tiny():
    import jax.numpy as jnp

    from ray_tpu.models import llama

    return llama.LlamaConfig.tiny(dtype=jnp.float32, max_seq=256)


def test_serve_phase_at_tiny_size(cpu_jax):
    result, tokens = chip_smoke.serve_phase(
        _tiny(), seed=0, num_kv_blocks=128, prompt_lens=(160, 96, 40),
        max_tokens=6)
    assert result["attention_impl"] == "reference"   # the CPU's choice
    assert result["tick_kinds"] == ["mixed"]
    assert result["step_compiles_after_warmup"] == 0
    assert result["prefix_tokens_saved_by_repeat"] >= 2 * 144
    assert result["warmup_shapes"] > 0 and result["warmup_s"] > 0
    assert len(tokens) == 3


def test_train_phase_at_tiny_size(cpu_jax):
    from ray_tpu.parallel.mesh import MeshConfig

    kw = dict(seed=0, batch=4, seq=64, steps=3, lr=1e-2)
    one = chip_smoke.train_phase(_tiny(), mesh_config=MeshConfig(), **kw)
    four = chip_smoke.train_phase(
        _tiny(), mesh_config=MeshConfig(fsdp=2, tp=2), **kw)
    assert one["losses"][-1] < one["losses"][0]
    assert len(four["param_devices"]) == 4 and len(one["param_devices"]) == 1
    assert four["collectives"]["all-gather"] > 0
    for a, b in zip(four["losses"], one["losses"]):
        assert abs(a - b) / b < 1e-3


def test_script_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    for line in proc.stdout.splitlines():
        assert json.loads(line).get("ok") is not True


def test_long_context_check_at_tiny_size(cpu_jax):
    """The comparison past the window that `chip_smoke.py` runs at the
    published widths, here at the tiny ones (window 8, 40 + 4 positions):
    the sound program agrees with the reference, every control does not."""
    from ray_tpu.models.phi4flash import Phi4FlashConfig

    result = chip_smoke.long_context_check(
        Phi4FlashConfig.tiny(), seed=3, n_prompt=40, n_decode=4, chunk=16,
        block_size=4, num_blocks=64, attention_impl="reference")
    assert result["rel_err"] < 2e-5
    assert set(result["controls"]) == set(chip_smoke.LONG_CONTROLS)
    assert all(err > 1e-4 for err in result["controls"].values()), result


def test_sampler_filter_timing_at_tiny_size(cpu_jax):
    """The stand-alone timing of the sampling head's filter, here at a tiny
    shape: every mix of what the rows ask for runs and keeps what it should
    (the times are the chip's to give)."""
    result = chip_smoke.sampler_filter_timing([(4, 300)], seed=1, calls=2)
    cell = result["4x300"]
    assert set(chip_smoke.SAMPLER_ASKS) <= set(cell)
    assert cell["neither_kept"] == 4 * 300 and cell["top_k_kept"] == 4 * 50
    assert 4 <= cell["both_kept"] <= cell["top_p_kept"] < 4 * 300
    assert cell["both_kept"] <= cell["top_k_kept"]


def test_power_retention_timing_at_tiny_size(cpu_jax):
    """The stand-alone timing of the power-retention kernel (`--phase
    power_retention`), here interpreted at a tiny shape: decode rows alone
    and rows beside one slice both agree with the `lax.scan` oracle, and so
    does a run of decode rows across two folds; the decode rows are timed
    with no fold in a call and with every sequence's (the times are the
    chip's to give)."""
    from ray_tpu.ops import power_retention as pr

    result = chip_smoke.power_retention_timing(
        ((3, 0), (3, 10)), seed=1, heads=6, kv_heads=2, head_dim=16,
        layers=2, calls=1, run_rows=3)
    assert set(result) == {"3+0", "3+10", "3+0.fold", "run"}
    for name in ("3+0", "3+10"):
        cell = result[name]
        assert cell["o_err"] < 2e-5 and cell["state_err"] < 2e-5
        assert cell["ms"] > 0 and cell["gb_s"] >= 0 and cell["us_step"] > 0
    assert result["3+0.fold"]["us_step"] > 0
    run, fold = result["run"], pr.fold_rows(16)
    assert run["steps"] == 2 * fold + 3 and run["folds"] == 2
    assert run["fill"] == 3
    assert run["o_err"] < 2e-5 and run["state_err"] < 2e-5


def test_retention_check_at_tiny_size(cpu_jax):
    """What `--phase retention_check` runs at Brumby's published widths, here
    at the tiny ones: the sound program agrees with the reference, every
    control of the reference does not, and a PROGRAM whose state is rounded
    to bfloat16 after every step reads over 1e-4 (its float32 state 1e-6)."""
    from ray_tpu.models.brumby import BrumbyConfig

    kw = dict(seed=3, n_prompt=40, n_decode=4, chunk=16, block_size=4,
              num_blocks=64, attention_impl="reference")
    sound = chip_smoke.long_context_check(
        BrumbyConfig.tiny(), controls=chip_smoke.RETENTION_CONTROLS, **kw)
    assert sound["rel_err"] < 2e-5
    assert set(sound["controls"]) == set(chip_smoke.RETENTION_CONTROLS)
    assert all(err > 1e-2 for err in sound["controls"].values()), sound
    bf16 = chip_smoke.long_context_check(
        BrumbyConfig.tiny(), controls=(), state_mantissa_bits=7, **kw)
    assert bf16["rel_err"] > 1e-4 and bf16["controls"] == {}


def test_kda_check_at_tiny_size(cpu_jax):
    """What `--phase kda_check` runs at Kimi-Linear's published widths, here
    at the tiny ones, the reference following the program's experts: the
    sound program agrees with it at every length, every control of the
    reference does not, and a PROGRAM whose state is rounded to bfloat16
    after every step reads over 1e-4 (its float32 state 2e-6), rounded a
    slice as rounded a token."""
    from ray_tpu.models.kimi_linear import KimiLinearConfig

    result = chip_smoke.kda_check(
        KimiLinearConfig.tiny(), seed=3, n_prompt=32, long_prompt=48,
        long_decode=24, chunk=16, block_size=4, num_blocks=64,
        attention_impl="reference")
    assert max(result["rel_err"], result["long_rel_err"],
               result["decode_rel_err"]) < 2e-5
    assert (result["long_positions"], result["decode_positions"]) == (56, 56)
    assert set(result["controls"]) == set(chip_smoke.KDA_CONTROLS)
    assert all(err > 1e-2 for err in result["controls"].values()), result
    assert result["bf16_state_long_rel_err"] > 1e-4
    assert result["bf16_state_decode_rel_err"] > 1e-4


def test_kimi_cut_is_the_cells_configuration():
    """`KIMI_CUT` (the one statement of the cell's cut outside the benchmark:
    the compile tests import it) names the layers, the held experts and the
    vocabulary slice of benchmarks/configs/kimi-linear-48b-l12-e32.json."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kimi-linear-48b-l12-e32.json")) as f:
        sizes = json.load(f)["sizes"]
    lin = sizes["linear_attn_config"]
    first = sizes["first_held_expert"]
    assert chip_smoke.KIMI_CUT == dict(
        num_hidden_layers=sizes["num_hidden_layers"],
        kda_layers=tuple(lin["kda_layers"]),
        full_attn_layers=tuple(lin["full_attn_layers"]),
        experts_held=(first, first + sizes["num_experts"]),
        vocab_size=sizes["vocab_size"])


def test_kda_timing_at_tiny_size(cpu_jax):
    """What `--phase kda` runs at Kimi-Linear's widths, here at 4 heads of
    16 with the kernel interpreted: kernel and oracle agree on outputs and
    on what state, buffer and fill fold to at every shape (the times are the
    chip's to give)."""
    result = chip_smoke.kda_timing(((3, 0), (3, 70), (0, 9)), seed=1, heads=4,
                                   head_dim=16, layers=2, calls=1)
    assert set(result) == {"3+0", "3+70", "0+9"}
    for cell in result.values():
        assert cell["o_err"] < 2e-5 and cell["state_err"] < 2e-5
        assert cell["ms"] > 0 and cell["hbm_share"] >= 0
        assert cell["kernel_ms"] is None       # no device plane off the chip


def test_kda_decode_sweep_at_tiny_size(cpu_jax):
    """`--phase kda`'s sweep of folds at tiny size: rows that cross two folds
    are the oracle's, the mix is timed alone and beside a slice, the state's
    index map is the module's again afterwards."""
    from ray_tpu.ops import kda

    block = kda.state_block
    result = chip_smoke.kda_decode_sweep(
        (4,), seed=3, rows=2, blocks=1, piece=9, heads=4, head_dim=16,
        layers=2, chunk=8, sub=8)
    assert kda.state_block is block and set(result) == {"4"}
    for fold, cell in result.items():
        assert cell["o_err"] < 2e-5 and cell["state_err"] < 2e-5
        assert cell["fill"] == (2 * int(fold) + 3) % int(fold)
        assert cell["join_step_no_state_dma_us"] > 0
        assert cell["beside_a_slice_ms"] > 0


def test_latent_kernel_timing_at_tiny_size(cpu_jax, monkeypatch):
    """What `--phase latent` times at DeepSeek-V2's and Kimi-Linear's widths,
    here at 8 and 4 heads of 128 lanes, float32, tiles of 2 pages of 4, with
    the kernel interpreted: decode rows over several tiles, the same beside a
    slice, and the reference's answer a sequence at a time (the times and
    the GB/s are the chip's to give)."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "latent_q_block", lambda heads, width: 4)
    monkeypatch.setattr(pa, "latent_kv_pages", lambda *a: (2, 1))
    shapes = {
        "a": dict(heads=8, layers=2, rows=3, context=(17, 40)),
        "a+slice": dict(heads=8, layers=2, rows=3, context=(17, 40), piece=9),
        "b": dict(heads=4, layers=3, rows=2, context=(3, 12))}
    result = chip_smoke.latent_kernel_timing(
        shapes, seed=5, width=128, lat=64, pages=64, block_size=4, calls=1,
        dtype=jnp.float32)
    assert set(result) == set(shapes)
    for cell in result.values():
        assert cell["err"] < 1e-5
        assert cell["ms"] > 0 and cell["gb_s"] >= 0 and cell["lower_s"] > 0
        assert cell["kernel_ms"] is None       # no device plane off the chip
    assert "slice_ms" in result["a+slice"] and "slice_ms" not in result["a"]


def test_mimo_kernel_timing_at_tiny_size(cpu_jax):
    """What `--phase kernels` times at MiMo-V2-Flash's widths, here at 8
    heads of 24 with the kernel interpreted: the three shapes run over the
    pools as the model declares them, whose rows hold no lane of padding (the
    times and the shares of the bandwidth are the chip's to give)."""
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models.mimo_v2_flash import MimoV2FlashConfig

    tiny = MimoV2FlashConfig.tiny(dtype=jnp.bfloat16)
    cut = {f.name: getattr(tiny, f.name) for f in dataclasses.fields(tiny)}
    result = chip_smoke.mimo_kernel_timing(
        seed=3, rows=3, context=700, piece=24, pages=256, block_size=4,
        calls=1, **cut)
    assert set(result) == {"full_decode", "full_decode+slice",
                           "window_decode"}
    for cell in result.values():
        assert cell["ms"] > 0
        assert 0 < cell["gb_s_useful"] == cell["gb_s_as_rows_lie"]
    assert result["full_decode+slice"]["slice_blocks"] >= 1


def test_table_decode_timing_at_tiny_size(cpu_jax):
    """What `--phase kernels` times of MiniCPM-SALA's decode stage, here at 4
    / 2 heads of 128 over pages of 4 with the kernel interpreted: 3 rows x 2
    kv heads walk 6 kept blocks of 4 pages each, two layers (the times are
    the chip's to give; off the chip no kernel event is found and the rates
    stand over the host's clock)."""
    result = chip_smoke.table_decode_timing(
        seed=3, rows=3, heads=4, kv_heads=2, layers=2, pages=64, block_size=4,
        block=16, topk=6, context=700, calls=1, interpret=True)
    assert result["kernel_ms_a_tick"] is None and result["ms_a_tick"] > 0
    # a row's last block holds the pages up to its own place
    assert 2 * 2 * 3 * 2 * 21 <= result["page_dmas_a_tick"] <= 2 * 2 * 3 * 2 * 24
    assert result["ns_a_page_dma"] > 0 and result["gb_s_as_rows_lie"] > 0


def test_kv5d_decode_timing_at_tiny_size(cpu_jax):
    """What `--phase kernels` times of the 5-D kernel at Mistral-7B's decode
    rows, here at 4 / 2 heads of 16 over pages of 4 with the kernel
    interpreted."""
    result = chip_smoke.kv5d_decode_timing(
        seed=3, rows=3, heads=4, kv_heads=2, head_dim=16, layers=2, pages=64,
        block_size=4, context=(20, 90), calls=1, interpret=True)
    assert result["kernel_ms"] is None and result["ms"] > 0
    assert 3 * 5 <= result["pages_a_layer"] <= 3 * 23
    assert result["ns_a_page"] > 0 and result["gb_s"] > 0
    assert result["pages_a_step"] >= 1


def test_glm_dsa_timing_at_tiny_size(cpu_jax):
    """What `--phase glm_dsa` runs at the cell's sizes, here over 64 pages
    and 128-lane rows with the kernel interpreted: the gather at every row
    width of the same pool's bytes, and the attention kernel on a lane block
    of a group's operand against the jnp form on that block sliced out (the
    times are the chip's to give)."""
    gather = chip_smoke.glm_dsa_gather_timing(
        (8, 3), seed=1, pages=64, page=16, topk=32, width=128, layers=4,
        context=500)
    assert set(gather) == {f"{t}x{b}" for t in (8, 3) for b in (256, 512,
                                                              1024)}
    for cell in gather.values():
        assert cell["ms"] > 0 and cell["ns_row"] > 0
        assert cell["device_ms"] is None       # no device plane off the chip
    attend = chip_smoke.glm_dsa_attend_timing(
        (8, 3), seed=1, heads=4, width=128, lat=64, topk=32, group_size=4,
        interpret=True)
    assert set(attend) == {"8", "3"}
    for cell in attend.values():
        assert cell["err"] < 2e-2      # bf16 operands, float32 sums
        assert cell["wide_ms"] > 0 and cell["wide_kernel_ms"] is None
    # the index entry alone: 6 rows on 2 shared runs of 8 pages (2 of the
    # kernel's tiles here), with a slice, and on tables that share nothing
    from ray_tpu.ops import sparse_latent as sl
    with mock.patch.object(sl, "INDEX_TILE", 64):
        index = chip_smoke.glm_dsa_index_timing(
            seed=1, rows=6, split=(4, 2), run_pages=8, tail=(1, 4),
            slice_tokens=20, heads=4, dim=128, page=16, pages=96, width=16,
            interpret=True)
    assert set(index) == {"decode", "decode+slice", "unshared",
                          "unshared+slice"}
    for name, cell in index.items():
        assert cell["ms"] > 0 and cell["kernel_ms"] is None
        assert cell.get("err", 0.0) < 1e-3, (name, cell)


def test_glm_dsa_check_at_tiny_size(cpu_jax):
    """What `--phase glm_dsa_check` runs at GLM-5.2's published widths, here
    at the tiny ones (a selection of 8 rows, 48 + 8 positions): the sound
    program agrees with the reference selecting for itself and following the
    program's rows, keeps the reference's own rows, not the most recent ones,
    and both controls of the program fail the limits."""
    from ray_tpu.models.glm_dsa import GlmDsaConfig

    result = chip_smoke.glm_dsa_check(
        GlmDsaConfig.tiny(), seed=3, n_prompt=48, n_decode=8, chunk=16,
        block_size=4, num_blocks=64, attention_impl="reference")
    assert result["passes"] and result["positions"] == 56
    assert max(result["rel_err"], result["rel_err_following"]) < 2e-5
    assert result["selection_overlap"] == [1.0, 1.0]
    assert max(result["selection_gap"]) <= 0.0
    assert result["selected_tokens"] == 56 - 8      # every row past 8
    assert max(result["recent_share"]) < 0.6
    assert set(result["controls"]) == set(chip_smoke.DSA_CONTROLS)
    for name, control in result["controls"].items():
        assert not control["passes"], name
        assert control["rel_err"] > 1e-2, name
    recent = result["controls"]["recent_rows"]
    assert recent["recent_share"] == [1.0, 1.0]
    assert recent["rel_err_following"] < 2e-5       # the rows it said it kept
    assert min(recent["selection_overlap"]) < 0.9


def test_glm_cut_is_the_cells_configuration():
    """`GLM_CUT` (the one statement of the cell's cut outside the benchmark:
    the compile tests import it) names the layers, the held experts, the
    vocabulary slice and the block table of benchmarks/configs/
    glm-5.2-l8-e8.json."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-5.2-l8-e8.json")) as f:
        sizes = json.load(f)["sizes"]
    cut = chip_smoke.GLM_CUT
    assert cut["num_hidden_layers"] == sizes["num_hidden_layers"] == 8
    assert list(cut["indexer_types"]) == sizes["indexer_types"]
    assert list(cut["mlp_layer_types"]) == sizes["mlp_layer_types"]
    assert cut["experts_held"] == (
        sizes["first_held_expert"],
        sizes["first_held_expert"] + sizes["n_routed_experts"])
    assert cut["vocab_size"] == sizes["vocab_size"]
    assert cut["max_position_embeddings"] == sizes["max_position_embeddings"]


def test_nemotron_h_check_at_tiny_size(cpu_jax):
    """What `--phase nemotron_h_check` runs at Nemotron-3-Super's published
    widths, here at the tiny ones, the reference following the program's
    experts: the sound program agrees with it with no choice that falls
    short, and every control of the reference does not."""
    from ray_tpu.models.nemotron_h import NemotronHConfig

    result = chip_smoke.long_context_check(
        NemotronHConfig.tiny(), seed=3, n_prompt=32, n_decode=12, chunk=16,
        block_size=4, num_blocks=64, attention_impl="reference",
        controls=chip_smoke.NEMOTRON_CONTROLS)
    assert result["rel_err"] < 2e-5 and result["positions"] == 44
    assert result["routed_choices"] == 2 * 2 * 44
    assert result["shortfall_max"] == 0.0 and result["routed_differ"] == 0
    assert set(result["controls"]) == set(chip_smoke.NEMOTRON_CONTROLS)
    assert all(err > 5e-2 for err in result["controls"].values()), result


def test_one_group_shortfall_by_hand():
    """Two token-layers over four experts, two kept: the first keeps the
    reference's own pair, the second keeps the expert ranked third for the
    one ranked second and falls short by 1 - 0.6 / 0.8."""
    scores = [[[[0.9, 0.8, 0.6, 0.1], [0.9, 0.8, 0.6, 0.1]]]]
    kept = [[[[1, 0], [0, 2]]]]
    result = chip_smoke.one_group_shortfall(scores, kept)
    assert abs(result.pop("shortfall_max") - 0.25) < 1e-12
    assert result == {"routed_choices": 2, "routed_differ": 1}


def test_nemotron_cut_is_the_cells_configuration():
    """`NEMOTRON_CUT` (the one statement of the cell's cut outside the
    benchmark: the compile tests import it) names the layers, the held experts
    and the vocabulary slice of benchmarks/configs/
    nemotron-3-super-l11-e128.json."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-3-super-l11-e128.json")) as f:
        sizes = json.load(f)["sizes"]
    first = sizes["first_held_expert"]
    assert chip_smoke.NEMOTRON_CUT == dict(
        num_hidden_layers=sizes["num_hidden_layers"],
        hybrid_override_pattern=sizes["hybrid_override_pattern"],
        experts_held=(first, first + sizes["n_routed_experts"]),
        vocab_size=sizes["vocab_size"])


@pytest.mark.parametrize("groups", [2, 8], ids=["grouped", "own_keys"])
def test_ssd_timing_at_tiny_size(cpu_jax, groups):
    """What `--phase ssd` runs at the published widths, here at 8 heads of 16
    in 2 groups, and with every head a group of its own, with the kernel
    interpreted: kernel and oracle agree on outputs and slots at every shape
    (the times are the chip's to give)."""
    result = chip_smoke.ssd_timing(((3, 0), (3, 20), (0, 9)), seed=1, heads=8,
                                   head_dim=16, groups=groups, d_state=16,
                                   layers=2, calls=1, chunk=8)
    assert set(result) == {"3+0", "3+20", "0+9"}
    for cell in result.values():
        assert cell["y_err"] < 2e-5 and cell["state_err"] < 2e-5
        assert cell["ms"] > 0 and cell["hbm_share"] >= 0
        assert cell["kernel_ms"] is None       # no device plane off the chip


def test_ssd_timing_crosses_folds_at_tiny_size(cpu_jax):
    """`calls` over the fold: the timed passes fold (a pass starts from
    empty buffers), and the oracle's check is of `ssd.folded`, at a fold the
    caller names."""
    result = chip_smoke.ssd_timing(((3, 0),), seed=2, heads=8, head_dim=16,
                                   groups=2, d_state=16, layers=2, calls=9,
                                   chunk=8, fold=4)
    assert result["3+0"]["y_err"] < 2e-5
    assert result["3+0"]["state_err"] < 2e-5


def test_ssd_decode_sweep_at_tiny_size(cpu_jax):
    """`--phase ssd`'s sweep of folds, two of them at tiny size: the mix is
    checked against the oracle at each, the state's index map is the
    module's again afterwards."""
    from ray_tpu.ops import ssd

    block = ssd.state_block
    result = chip_smoke.ssd_decode_sweep(
        (2, 4), seed=3, rows=2, blocks=2, heads=8, head_dim=16, groups=2,
        d_state=16, layers=2, chunk=8)
    assert ssd.state_block is block and set(result) == {"2", "4"}
    for cell in result.values():
        assert cell["y_err"] < 2e-5 and cell["state_err"] < 2e-5
        assert cell["join_step_no_state_dma_us"] > 0


def test_grouped_shapes_are_the_five_routed_cells():
    """`--phase grouped_dot` reads its shapes from the benchmark's files: the
    products, held experts and picks of the five cells whose traffic reaches
    `held_expert_ffn`."""
    got = {c: chip_smoke.grouped_shapes(c) for c in chip_smoke.GROUPED_CELLS}
    assert got["nemotron3super"] == {
        "products": [(1024, 2688), (2688, 1024)], "held": 128,
        "published": 512, "picks": 22, "dtype": "bfloat16",
        "rows": (64, 192)}
    assert got["kimilinear"]["products"] == [(2304, 1024)] * 2 + [(1024, 2304)]
    assert [(s["held"], s["published"], s["picks"]) for s in got.values()] == [
        (128, 512, 22), (32, 256, 8), (40, 160, 6), (16, 256, 8), (8, 256, 8)]


def test_grouped_dot_timing_at_tiny_size(cpu_jax):
    """What `--phase grouped_dot` runs at the published widths, here at 8
    held experts of 48 x 40 with the kernel interpreted: kernel and
    `ragged_dot` both agree with the loop-over-groups oracle and the rows
    behind the last group read zero (the times are the chip's to give)."""
    sizes = {"hidden_size": 48, "moe_intermediate_size": 40,
             "mlp_hidden_act": "relu2", "n_routed_experts": 8,
             "n_routed_experts_published": 16, "num_experts_per_tok": 4,
             "torch_dtype": "float32"}
    tiny = lambda cell: dict(chip_smoke.grouped_shapes(cell, sizes),
                             rows=(6, 14))
    result = chip_smoke.grouped_dot_timing(["nemotron3super"], seed=1,
                                           calls=1, shapes=tiny)
    assert set(result) == {"nemotron3super 48x40 6", "nemotron3super 48x40 14",
                           "nemotron3super 40x48 6", "nemotron3super 40x48 14"}
    for line in result.values():
        assert 0 < line["met"] <= 8 and line["pairs"] in (12, 28)
        for how in ("kernel", "ragged"):
            assert line[how]["err"] < 1e-5 and line[how]["behind"] == 0
            assert line[how]["ms"] > 0 and line[how]["op_ms"] is None
    swept = chip_smoke.grouped_dot_timing(["nemotron3super"], seed=1, calls=1,
                                          shapes=tiny, tiles=(8, 0))
    assert all("ragged" not in line and line["tiles"][0] == 8
               for line in swept.values())


def test_minicpm_sala_check_at_tiny_size(cpu_jax):
    """What `--phase minicpm_sala_check` runs at MiniCPM-SALA's published
    widths, at the tiny configuration in float32: a prompt of 2 x `dense_len`
    + and 8 decode rows with the selection followed agree in logits AND in the
    sparse layers' attention outputs with no choice short of the
    reference's; the reference attending densely does not agree in the
    attention outputs; the program with its page means wiped keeps other
    blocks, short of the reference's."""
    import chip_smoke
    from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig

    config = MiniCPMSALAConfig.tiny()
    sound = chip_smoke.minicpm_sala_check(
        config, seed=3, n_prompt=384, n_decode=8, chunk=32, num_blocks=64,
        watch_slices=4, attention_impl="reference")
    assert sound["rel_err"] < 2e-5 and sound["attended_err"] < 2e-5
    assert sound["attended_rows"] == 4 * 32 + 7
    assert sound["sets"] == 2 * 2 * (391 - 128) and sound["sets_differ"] == 0
    assert sound["shortfall_max"] == 0.0 and sound["watched_rows_select"]
    assert sound["dense_above"]["attended_err"] > 0.1
    behind = sound["means_left_behind"]
    assert behind["attended_err"] < 2e-5          # it FOLLOWS the program
    assert behind["sets_differ"] > 0 and behind["shortfall_max"] > 0.01


def test_minicpm_sala_cut_is_the_cells_configuration():
    import json
    import os

    import chip_smoke
    from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig

    with open(os.path.join(chip_smoke.ROOT, "benchmarks", "configs",
                           "minicpm-sala-l16.json")) as f:
        sizes = json.load(f)["sizes"]
    cut = MiniCPMSALAConfig(**chip_smoke.MINICPM_SALA_CUT)
    assert list(cut.mixer_types) == sizes["mixer_types"]
    assert (cut.num_hidden_layers, cut.first_published_layer) == (
        sizes["num_hidden_layers"], sizes["first_published_layer"])
    for key in ("hidden_size", "vocab_size", "topk", "dense_len",
                "window_size", "kernel_stride", "block_size"):
        assert getattr(cut, key) == sizes[key], key


def test_afmoe_kernel_timing_at_tiny_size(cpu_jax):
    """What `--phase afmoe_kernels` times at Trinity-Large-Preview's widths,
    here at 12 / 2 heads of 16 with the kernel interpreted: both forms,
    decode rows alone and beside a slice, over the pools as the model
    declares them, each against the jnp reference; and a sweep's tiles in
    place of `kv_sizes`' (the times are the chip's to give)."""
    import jax.numpy as jnp

    from ray_tpu.models.afmoe import AfmoeConfig
    from ray_tpu.ops import paged_attention as pa

    tiny = AfmoeConfig.tiny(dtype=jnp.bfloat16)
    was = pa.kv_sizes
    try:
        for tiles in (None, (2, 4)):
            result = chip_smoke.afmoe_kernel_timing(
                seed=3, rows=3, context=(60, 90), piece=24, pages=64,
                block_size=4, calls=1, tiles=tiles, config=tiny)
            assert set(result) == {
                "pages_a_step", "full_decode", "full_decode+slice",
                "window_decode", "window_decode+slice"}
            if tiles:
                assert result["pages_a_step"] == {"all": [2, 4],
                                                  "window": [2, 4]}
            for name, cell in result.items():
                if name != "pages_a_step":
                    assert cell["ms"] > 0 and cell["err"] < 2e-2, name
            assert result["window_decode+slice"]["slice_blocks"] >= 1
    finally:
        pa.kv_sizes = was


def test_afmoe_check_at_tiny_size(cpu_jax):
    """What `--phase afmoe_check` runs at Trinity-Large-Preview's published
    widths, at the tiny configuration in float32: a prompt of six windows and
    8 decode rows with the experts followed agree in logits AND in every
    layer's attention output with no choice short of the reference's; each
    of the six controls fails by one of the three limits; the prompt served
    twice through an engine is a hit on both groups the second time."""
    from ray_tpu.models.afmoe import AfmoeConfig

    result = chip_smoke.afmoe_check(
        AfmoeConfig.tiny(), seed=3, n_prompt=48, n_decode=8, chunk=16,
        num_blocks=64, watch_slices=2, block_size=4,
        attention_impl="reference")
    assert result["rel_err"] < 2e-5 and result["attended_err"] < 2e-5
    assert result["attended_rows"] == 2 * 16 + 7
    assert len(result["attended_err_by_layer"]) == 5
    assert result["routed_choices"] == 3 * 55 and result["routed_differ"] == 0
    assert set(result["controls"]) == set(chip_smoke.AFMOE_CONTROLS)
    for name, control in result["controls"].items():
        assert (control["rel_err"] > chip_smoke.LOGITS_REL_TOL
                or control["attended_err"] > chip_smoke.ATTENDED_REL_TOL
                or control["shortfall_max"] > chip_smoke.ROUTING_TIE_MARGIN
                ), name
    assert result["controls"]["no_window"]["attended_err"] > 0.1
    assert result["controls"]["full_rotated"]["attended_err"] > 0.1
    hit = result["hit"]
    assert hit["tokens_equal"] and hit["first_token_is_the_steps"]
    assert hit["prefix_hits"] == 1 and hit["prefix_hits_cut_short"] == 0
    assert hit["prefix_tokens_saved"] == 44     # (48 - 1) // 4 pages
    assert hit["window_tail_pages"] == 2


def test_afmoe_cut_is_the_cells_configuration():
    """`AFMOE_CUT` (the one statement of the cell's cut outside the
    benchmark: the compile tests import it) names the layers, the held
    experts and the vocabulary slice of benchmarks/configs/
    trinity-large-l5-e32.json."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "trinity-large-l5-e32.json")) as f:
        sizes = json.load(f)["sizes"]
    first = sizes["first_held_expert"]
    assert chip_smoke.AFMOE_CUT == dict(
        vocab_size=sizes["vocab_size"],
        num_dense_layers=sizes["num_dense_layers"],
        experts_held=(first, first + sizes["num_experts"]),
        layer_types=tuple(sizes["layer_types"]))


def test_lfm2_kernel_timing_at_tiny_size(cpu_jax):
    """What `--phase lfm2_kernels` times at LFM2-24B-A2B's widths, here at 8 /
    4 heads of 64 (two kv pairs, runs of two) with the kernel interpreted:
    decode rows at two contexts, a tick's decode rows alone and beside a
    slice, over the pools as the model declares them, each through
    `pair_queries` / `pair_outputs` against PLAIN grouped-query attention;
    a sweep's tiles in place of `kv_sizes`'; and the grouped product at every
    expert held (the times are the chip's to give)."""
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig
    from ray_tpu.ops import paged_attention as pa

    tiny = Lfm2MoeConfig.tiny(dtype=jnp.bfloat16)
    was = pa.kv_sizes
    try:
        for tiles in (None, (2, 4)):
            result = chip_smoke.lfm2_kernel_timing(
                seed=3, rows=3, contexts=(40, 90), mix=(20, 90), piece=24,
                pages=64, block_size=4, calls=1, tiles=tiles, config=tiny)
            assert set(result) == {"pages_a_step", "decode_40", "decode_90",
                                   "tick_decode", "tick"}
            if tiles:
                assert result["pages_a_step"] == [2, 4]
            for name, cell in result.items():
                if name != "pages_a_step":
                    assert cell["ms"] > 0 and cell["err"] < 2e-2, name
            assert result["tick"]["slice_blocks"] == 1
    finally:
        pa.kv_sizes = was
    sizes = {"hidden_size": 128, "moe_intermediate_size": 256,
             "num_experts": 8, "num_experts_published": 8,
             "num_experts_per_tok": 4, "torch_dtype": "float32"}
    tiny_shapes = lambda cell: dict(
        chip_smoke.lfm2_grouped_shapes(cell, sizes), rows=(6, 14))
    result = chip_smoke.grouped_dot_timing(["lfm2moe"], seed=1, calls=1,
                                           shapes=tiny_shapes)
    assert set(result) == {"lfm2moe 128x256 6", "lfm2moe 128x256 14",
                           "lfm2moe 256x128 6", "lfm2moe 256x128 14"}
    for line in result.values():
        assert line["pairs"] in (24, 56)        # every pick: all are held
        assert line["kernel"]["err"] < 1e-5 and line["kernel"]["behind"] == 0


def test_lfm2_cut_is_the_cells_configuration():
    """`LFM2_CUT` (the one statement of the cell's cut outside the benchmark:
    the compile tests import it) names the layers of benchmarks/configs/
    lfm2-24b-a2b-l9.json, and the phase's grouped shapes are its experts':
    all 64 held, 4 and 12 pairs a group."""
    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-24b-a2b-l9.json")) as f:
        sizes = json.load(f)["sizes"]
    assert chip_smoke.LFM2_CUT == dict(
        num_dense_layers=sizes["num_dense_layers"],
        layer_types=tuple(sizes["layer_types"]))
    cut = Lfm2MoeConfig(max_position_embeddings=8192, **chip_smoke.LFM2_CUT)
    assert cut.layer_types == Lfm2MoeConfig().layer_types[1:10]
    for key in ("hidden_size", "vocab_size", "num_experts", "head_dim",
                "intermediate_size", "moe_intermediate_size"):
        assert getattr(cut, key) == sizes[key], key
    assert cut.experts_held == (0, sizes["num_experts_published"])
    shape = chip_smoke.lfm2_grouped_shapes("lfm2moe")
    assert shape == {"products": [(2048, 1536), (2048, 1536), (1536, 2048)],
                     "held": 64, "published": 64, "picks": 4,
                     "dtype": "bfloat16", "rows": (64, 192)}
    assert "lfm2_kernels" in chip_smoke.CHILDREN
