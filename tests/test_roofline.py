"""HLO collective parser + roofline arithmetic."""

from repro.launch import roofline

HLO = """
ENTRY main {
  %p = bf16[32,1024]{1,0} parameter(0)
  %ag = bf16[512,1024]{1,0} all-gather(bf16[32,1024]{1,0} %p), dimensions={0}
  %ar.1 = f32[16,4096]{1,0} all-reduce(f32[16,4096]{1,0} %x), to_apply=%add
  %ars = f32[8,8]{1,0} all-reduce-start(f32[8,8]{1,0} %y), to_apply=%add
  %ard = f32[8,8]{1,0} all-reduce-done(f32[8,8]{1,0} %ars)
  %a2a = bf16[4,256]{1,0} all-to-all(bf16[4,256]{1,0} %z), dimensions={0}
  %cp = u8[1000]{0} collective-permute(u8[1000]{0} %w), source_target_pairs={{0,1}}
}
"""


def test_collective_bytes_parsing():
    out = roofline.collective_bytes(HLO)
    assert out["all-gather"] == 32 * 1024 * 2
    assert out["all-reduce"] == 16 * 4096 * 4 + 8 * 8 * 4  # -done not counted
    assert out["all-to-all"] == 4 * 256 * 2
    assert out["collective-permute"] == 1000
    assert out["count"] == 5
    assert out["total"] == sum(
        out[k] for k in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute")
    )


def test_roofline_terms_and_dominant():
    rl = roofline.Roofline(
        arch="a", shape="s", mesh="16x16", chips=256,
        flops_per_device=197e12,        # exactly 1s compute
        bytes_per_device=819e9 * 2,     # 2s memory
        coll_bytes_per_device=50e9 * 0.5,
        model_flops=197e12 * 256,       # == chips x peak x 1s
        coll_breakdown={},
    )
    assert abs(rl.compute_s - 1.0) < 1e-9
    assert abs(rl.memory_s - 2.0) < 1e-9
    assert abs(rl.collective_s - 0.5) < 1e-9
    assert rl.dominant == "memory"
    assert abs(rl.roofline_fraction - 0.5) < 1e-9  # bound by 2s memory
    assert abs(rl.useful_flops_ratio - 1.0) < 1e-9


def test_model_flops_kinds():
    from repro import configs

    cfg = configs.get_config("llama3-8b")
    tr = roofline.model_flops_for(cfg, configs.get_shape("train_4k"))
    pf = roofline.model_flops_for(cfg, configs.get_shape("prefill_32k"))
    dc = roofline.model_flops_for(cfg, configs.get_shape("decode_32k"))
    assert tr == 6.0 * cfg.active_param_count() * 256 * 4096
    assert pf == 2.0 * cfg.active_param_count() * 32 * 32768
    assert dc == 2.0 * cfg.active_param_count() * 128


def test_moe_uses_active_params():
    from repro import configs

    cfg = configs.get_config("deepseek-v2-236b")
    tr = roofline.model_flops_for(cfg, configs.get_shape("train_4k"))
    assert tr < 6.0 * cfg.param_count() * 256 * 4096 * 0.2


def test_peaks_keyed_by_device_kind():
    p = roofline.device_peaks("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bw == 819e9


def test_peaks_unknown_device_kind_raises():
    import pytest

    with pytest.raises(ValueError, match="no published peaks"):
        roofline.device_peaks("TPU v99")
    rl = roofline.Roofline(
        arch="a", shape="s", mesh="1", chips=1, flops_per_device=1.0,
        bytes_per_device=1.0, coll_bytes_per_device=0.0, model_flops=1.0,
        coll_breakdown={}, device_kind="cpu",
    )
    with pytest.raises(ValueError):
        rl.compute_s
