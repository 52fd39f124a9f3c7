"""(d) The plain references agree with the program at the ``rehearse`` sizes
on the cpu backend, in float32: forward, loss, gradient and update."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_resnet50_reference_matches_the_trainer_over_three_steps():
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    from benchmarks.drivers.train_vision import _strip_prefix

    cfg = manifest.load_json("configs", "resnet50_v1.json")
    sz = cfg["rehearse"]["sizes"]
    ref = manifest.load_module("reference", cfg["reference"])
    plan = manifest.load_module("generators", "steady_steps").generate(
        5, {"per_chip_batch": 8, "in_flight": 2, "warm_steps": 1}, sz, 1)
    data, label = jnp.asarray(plan["data"]), jnp.asarray(plan["label"])
    opt = sz["optimizer"]
    mx.config.set("kernels.enabled", False)
    try:
        mx.random.seed(5)
        net = vision.get_model(sz["model"], classes=sz["classes"])
        net.initialize(mx.init.Xavier())
        tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                         dict(opt), mesh=make_mesh({"dp": 1},
                                                   jax.devices()[:1]),
                         dtype="float32")
        got = [float(tr.step(data, label))]
        p = _strip_prefix({n: jnp.asarray(v.data()._data)
                           for n, v in net.collect_params().items()})
        held_one = _strip_prefix({n: jnp.array(v)
                                  for n, v in tr.params.items()})
        got += [float(tr.step(data, label)) for _ in range(2)]
    finally:
        mx.config.unset("kernels.enabled")
    layers = tuple(sz["layers"])
    mom = {n: jnp.zeros_like(v) for n, v in p.items() if ref.is_trainable(n)}
    step = jax.jit(lambda p, m: ref.sgd_step(
        p, m, data, label, layers, opt["learning_rate"], opt["momentum"],
        opt["wd"]))
    first = p
    want, after_one = [], None
    for _ in range(3):
        value, p, mom = step(p, mom)
        want.append(float(value))
        after_one = after_one or p
    # The forward: float32 on both sides, the loss agrees to rounding.
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    # Gradient and update, first step.  The classifier's leaves are well
    # conditioned and agree to rounding.  Further back, a cold-start
    # BatchNorm net at batch 8 amplifies float32 rounding layer by layer
    # (PERF.md 6, PR 21: two layouts of one program differ the same way):
    # the median leaf differs by 3% of its own update, so the bound on it
    # is loose; a wrong formula moves every leaf by its whole update.  The
    # convolution biases that feed a BatchNorm have a zero true gradient
    # and are left out.
    def rel(n):
        want_d = np.asarray(after_one[n] - first[n])
        got_d = np.asarray(held_one[n] - first[n])
        return np.linalg.norm(got_d - want_d) / np.linalg.norm(want_d)
    for n in ("dense0_weight", "dense0_bias"):
        assert rel(n) < 5e-3, (n, rel(n))
    leaves = [rel(n) for n in mom if not n.endswith("_bias")]
    assert np.median(leaves) < 0.1 and max(leaves) < 1.0, sorted(leaves)[-5:]
    # three steps: the same amplification, compounded through the update
    np.testing.assert_allclose(got, want, rtol=0.5)


def test_opt_reference_matches_the_program_forward():
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig
    cfg = manifest.load_json("configs", "opt_1p3b.json")
    lm = cfg["rehearse"]["sizes"]["lm"]
    ref = manifest.load_module("reference", cfg["reference"])
    model = TransformerLM(TransformerLMConfig(dtype=jnp.float32, **lm))
    params = model.init(jax.random.PRNGKey(3))
    tokens = np.random.default_rng(3).integers(0, lm["vocab_size"], (48,))
    got = np.asarray(model.apply(params, jnp.asarray(tokens)[None])[0])
    want = np.asarray(ref.logits(params, jnp.asarray(tokens, jnp.int32)))
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())
    # the served-token rule: the program's own greedy tokens have gap 0
    served = np.asarray(model.greedy_decode(params, tokens[:20], 6))
    gaps, absmax = ref.served_token_gaps(params, tokens[:20], served, 64, 12)
    assert gaps.shape == (6,) and float(gaps.max()) <= 1e-5 * float(absmax)
    # and a wrong token is caught
    wrong = served.copy()
    wrong[2] = (wrong[2] + 1) % lm["vocab_size"]
    gaps, absmax = ref.served_token_gaps(params, tokens[:20], wrong, 64, 12)
    assert float(gaps.max()) > 4 * 2.0 ** -8 * float(absmax)


def test_ops_and_bytes_from_shapes():
    r = manifest.load_module("ops_bytes", "resnet50_v1")
    sizes = manifest.load_json("configs", "resnet50_v1.json")["sizes"]
    # ResNet-50 v1 (stride on the first 1x1): 3.86 G multiply-adds an image
    assert abs(r.forward_macs_per_image(sizes) / 1e9 - 3.858) < 0.005
    assert r.step_flops(sizes, 128) == 6 * 128 * r.forward_macs_per_image(
        sizes)
    o = manifest.load_module("ops_bytes", "opt_1p3b")
    lm = manifest.load_json("configs", "opt_1p3b.json")["sizes"]["lm"]
    assert abs(o.parameter_count(lm) / 1e9 - 1.3158) < 0.001
    assert o.kv_bytes_per_token(lm) == 192 * 1024
    assert o.decode_iteration_bytes(lm, 0, 0) == o.weight_bytes(lm)
