"""The comparison that decides ``correct``.

Each sampled request's image from the timed path is compared with the
configuration's plain reference run on the same prompt, negative
prompt, guidance and noise seed, with weights the benchmark makes again
from the seed (the program's are freed first).  A cell compares one of
two numbers, as ``bench/checks/<cell>.json`` names it, with the limit
and the readings it was set from:

* ``image_rel_err``: the worst over the sample of
  ``||image - reference|| / ||reference||`` over all pixels;
* ``image_err_ratio``: the worst over the sample of that error over the
  error of the reference computed at the configuration's own precision
  (bfloat16 matmul inputs).  Many denoising steps under strong guidance
  amplify every rounding by a gain that differs from seed to seed; the
  ratio takes the gain out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@functools.lru_cache(maxsize=8)
def _ref_fn(reference, spec_json: str, mode: str, sampler: str,
            steps: int, guidance: float, has_neg: bool):
    import json
    spec = json.loads(spec_json)
    net = reference.Net(mode)

    def fn(w, tokens, neg, noise):
        return reference.generate(net, w, spec, tokens,
                                  neg if has_neg else None, guidance, noise,
                                  sampler, steps)
    return jax.jit(fn)


def reference_images(family, reference, spec, make, key, reqs,
                     modes=("f32",)) -> dict:
    """mode -> rid -> image (float32 numpy) for ``reqs``: ``"f32"`` the
    reference, ``"bf16"`` the reference at the configuration's own
    precision, ``"lower"`` the control."""
    import json
    plain = family.plain(make(key))
    spec_json = json.dumps(spec, sort_keys=True)
    out = {}
    for build, group in ((reference.file_weights, ("f32", "bf16")),
                         (reference.control_weights, ("lower",))):
        todo = [m for m in modes if m in group]
        if not todo:
            continue
        w = build(plain, spec["weight_formats"])
        with jax.default_matmul_precision("highest"):
            for mode in todo:
                out[mode] = {r["rid"]: _image(reference, spec_json, mode, w, r)
                             for r in reqs}
        del w
    return out


def _image(reference, spec_json, mode, w, r) -> np.ndarray:
    fn = _ref_fn(reference, spec_json, mode, r["sampler"], r["steps"],
                 r["guidance"], r["neg_tokens"] is not None)
    hw = r["latent_hw"]
    noise = jax.random.normal(jax.random.PRNGKey(r["seed"]), (hw, hw, 4),
                              jnp.float32)
    neg = (jnp.asarray(r["neg_tokens"], jnp.int32)
           if r["neg_tokens"] is not None
           else jnp.zeros((len(r["tokens"]),), jnp.int32))
    return np.asarray(fn(w, jnp.asarray(r["tokens"], jnp.int32), neg, noise),
                      np.float32)


def modes_for(number: str) -> tuple:
    return ("f32",) if number == "image_rel_err" else ("f32", "bf16")


def numbers(number: str, got: dict, refs: dict) -> dict:
    """rid -> the cell's number for the images ``got`` (the program's, or
    the control's) against the reference images ``refs``."""
    ref = refs["f32"]
    err = {rid: rel_err(got[rid], ref[rid]) for rid in ref}
    if number == "image_rel_err":
        return err
    return {rid: err[rid] / rel_err(refs["bf16"][rid], ref[rid])
            for rid in ref}


def readings(family, reference, spec, make, key, reqs, got,
             number: str) -> dict:
    """rid -> the cell's number for the program's images ``got``."""
    refs = reference_images(family, reference, spec, make, key, reqs,
                            modes_for(number))
    return numbers(number, got, refs)


def judge(readings: dict, checks: dict) -> dict:
    """``correct`` and the printed lines: each number beside its limit."""
    (number, spec), = checks["numbers"].items()
    lim = spec["limit"]
    worst = max(readings.values()) if readings else float("inf")
    ok = bool(readings) and np.isfinite(worst) and worst <= lim
    value = worst if np.isfinite(worst) else 1e30
    return {"correct": bool(ok),
            "checks": {number: {"value": value, "limit": lim},
                       "images_compared": {"value": len(readings),
                                           "limit": 1}},
            "lines": [f"images_compared {len(readings)} limit >= 1",
                      f"{number} {value!r} limit {lim!r}"]}
