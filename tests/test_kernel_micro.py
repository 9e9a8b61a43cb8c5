"""tools/kernel_micro.py rehearsed on the CPU: one wave of each Mosaic kernel
at a tiny shape, interpreted, this checkout against itself as the baseline;
every measurement's outputs bit-equal and the pair-kind tables moving no
row."""
import json
import os

from tools import kernel_micro

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_micro_run_rehearses_on_the_cpu(capsys):
    assert kernel_micro.main(["--cpu", "--cell", "higgs_binary",
                              "--baseline", REPO]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    by_kernel = {ln.get("kernel"): ln for ln in lines if "kernel" in ln}
    assert set(by_kernel) == {"compact", "compact_blocks_sweep",
                              "compact_pair_kinds", "hist_wave", "hist_root"}
    for name in ("compact", "hist_wave", "hist_root"):
        assert by_kernel[name]["bit_equal"] is True, name
    sweep = by_kernel["compact_blocks_sweep"]
    assert {"R1_ring8", "R4_ring8", "R8_ring4", "R8_ring16"} <= set(sweep)
    assert all(v["bit_equal"] is True for k, v in sweep.items()
               if k.startswith("R")), sweep
    kinds = by_kernel["compact_pair_kinds"]
    assert kinds["outputs_identity"] is True
    assert {"us_per_copy_pair", "us_per_copy_pair_baseline"} <= set(kinds)
    wave = next(ln for ln in lines if "pairs" in ln and "kernel" not in ln)
    assert 0 < wave["pairs"] <= wave["table_steps"]
    assert wave["copy"] + wave["permute"] + wave["duplicate"] == wave["pairs"]
    compact = by_kernel["compact"]
    assert compact["grid_steps"] == -(-wave["tiles"] // compact["run_tiles"])
