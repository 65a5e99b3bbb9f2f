"""A run with the timed path broken underneath must come out not
correct: an image altered where it is produced, a denoise step left
out, and images handed to the wrong requests of a batch."""
import pytest

from perfbench_fixtures import run_cell


def broken_denoise(monkeypatch, fault):
    from repro.engine import diffusion_engine as de
    orig = de.build_denoise

    def build(cfg, sampler, use_cfg, **kw):
        fn = orig(cfg, sampler, use_cfg, **kw)

        def run(params, toks, negs, scales, noise, plan):
            if fault == "skip_step":
                plan = dict(plan, valid=plan["valid"].at[0].set(False))
            out = fn(params, toks, negs, scales, noise, plan)
            if fault == "altered":
                out = out * 0.9
            if fault == "swapped":
                out = out[::-1]
            return out
        return run
    monkeypatch.setattr(de, "build_denoise", build)


@pytest.mark.parametrize("cell,fault", [
    ("tiny.turbo", "altered"), ("tiny.turbo", "skip_step"),
    ("tiny.offline", "altered"), ("tiny.offline", "skip_step"),
    ("tiny.offline", "swapped")])
def test_broken_path_is_not_correct(tiny_root, capsys, monkeypatch, cell,
                                    fault):
    broken_denoise(monkeypatch, fault)
    out = run_cell(tiny_root, capsys, cell, seconds=1.0)
    assert out["correct"] is False
    chk = out["checks"]["image_rel_err"]
    assert chk["value"] > chk["limit"]
