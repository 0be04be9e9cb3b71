"""The writers' files load through the program's normal loaders, and each
FLOP count agrees with the compiler's count of its reference's matmuls."""

import numpy as np
import pytest

from benchmark import harness


def test_cifar_writer_loads_through_the_programs_loader(tmp_path):
    from heterofl_tpu.data.datasets import _load_cifar_bin

    sizes = {"train": 500, "test": 100, "classes": 10}
    write = harness.load_module("data", "cifar_bin").write
    write(str(tmp_path / "a"), "CIFAR10", 3000000019, sizes)
    write(str(tmp_path / "b"), "CIFAR10", 3000000019, sizes)
    write(str(tmp_path / "c"), "CIFAR10", 5, sizes)
    tr = _load_cifar_bin(str(tmp_path / "a" / "CIFAR10"), "train", "CIFAR10")
    te = _load_cifar_bin(str(tmp_path / "a" / "CIFAR10"), "test", "CIFAR10")
    assert tr.data.shape == (500, 32, 32, 3) and tr.data.dtype == np.uint8
    assert te.data.shape == (100, 32, 32, 3) and tr.classes_size == 10
    assert set(np.unique(tr.target)) == set(range(10))
    # what the loader returns is what the writer wrote, record for record
    raw = np.fromfile(tmp_path / "a" / "CIFAR10" / "cifar-10-batches-bin" /
                      "data_batch_1.bin", np.uint8).reshape(100, 3073)
    assert np.array_equal(raw[:, 0], tr.target[:100])
    assert np.array_equal(raw[:, 1:].reshape(100, 3, 32, 32).transpose(0, 2, 3, 1),
                          tr.data[:100])
    same = _load_cifar_bin(str(tmp_path / "b" / "CIFAR10"), "train", "CIFAR10")
    other = _load_cifar_bin(str(tmp_path / "c" / "CIFAR10"), "train", "CIFAR10")
    assert np.array_equal(same.data, tr.data)
    assert not np.array_equal(other.data, tr.data)
    # a class's images look alike: the loss can fall
    means = np.stack([tr.data[tr.target == c].mean(0) for c in range(10)])
    assert np.abs(means[0] - means[1]).mean() > 10.0


def test_wikitext_writer_gives_the_published_vocabulary(tmp_path):
    from heterofl_tpu.data.datasets import _load_lm

    sizes = harness.load_json("configs", "transformer-wikitext2.json")["data"]["sizes"]
    harness.load_module("data", "wikitext").write(str(tmp_path), "WikiText2", 11, sizes)
    tr = _load_lm(str(tmp_path / "WikiText2"), "train", "WikiText2")
    te = _load_lm(str(tmp_path / "WikiText2"), "test", "WikiText2")
    assert len(tr.vocab) == 33278
    assert tr.token.shape == (sizes["train"],) and te.token.shape == (sizes["test"],)
    assert len(np.unique(tr.token)) == 33277  # every type but <ukn>
    assert sizes["train"] // 100 == 55 * 64   # 55 whole windows a user
    counts = np.bincount(tr.token, minlength=33278)
    assert counts[1] == sizes["train"] // 33  # one <eos> a line
    assert np.sort(counts)[-2] > 100 * np.median(counts)  # Zipf: a long tail


def _matmul_flops(fn, *args):
    import jax

    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return float((cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"])


@pytest.mark.parametrize("rate", [1.0, 0.5])
def test_resnet_flops_against_the_compilers_count(rate):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import resnet18
    from benchmark.tests import tiny

    _, config = tiny.resnet()
    config["model"]["hidden_size"] = [16, 32, 64, 128]
    flops = harness.load_module("flops", "resnet18")
    model = dict(config["model"], classes=10)
    widths = [int(np.ceil(h * rate)) for h in model["hidden_size"]]
    shapes = {"conv1.w": (3, 3, 3, widths[0]), "n4.g": (widths[3],), "n4.b": (widths[3],),
              "linear.w": (widths[3], 10), "linear.b": (10,)}
    planes = widths[0]
    for s in range(4):
        for b in range(2):
            pre = f"layer{s}.{b}"
            if b == 0 and s > 0:
                shapes[f"{pre}.shortcut.w"] = (1, 1, planes, widths[s])
            shapes[f"{pre}.n1.g"] = shapes[f"{pre}.n1.b"] = (planes,)
            shapes[f"{pre}.conv1.w"] = (3, 3, planes, widths[s])
            shapes[f"{pre}.n2.g"] = shapes[f"{pre}.n2.b"] = (widths[s],)
            shapes[f"{pre}.conv2.w"] = (3, 3, widths[s], widths[s])
            planes = widths[s]
    p = {k: jnp.ones(v, jnp.float32) for k, v in shapes.items()}
    img = jnp.ones((4, 32, 32, 3), jnp.float32)
    got = _matmul_flops(lambda p_, x_: resnet18.forward(p_, x_, rate, (2, 2, 2, 2)), p, img)
    want = 4 * flops.forward_flops(model, [32, 32, 3], rate)
    # the compiler also counts norms and activations, and (the reference's
    # convolution being written as its taps) the zero border: some tenths
    # on top at these small widths
    assert want <= got <= 1.4 * want, (want, got)
    del jax


@pytest.mark.parametrize("rate", [1.0, 0.25])
def test_transformer_flops_against_the_compilers_count(rate):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import transformer

    model = {"embedding_size": 64, "num_heads": 4, "hidden_size": 128,
             "num_layers": 2, "bptt": 64, "num_tokens": 4000}
    flops = harness.load_module("flops", "transformer")
    shapes = {"embedding.tok.w": (4001, 64), "embedding.pos.w": (64, 64),
              "embedding.norm.g": (64,), "embedding.norm.b": (64,),
              "dec.l1.w": (64, 64), "dec.l1.b": (64,), "dec.norm.g": (64,),
              "dec.norm.b": (64,), "dec.l2.w": (64, 4000), "dec.l2.b": (4000,)}
    for i in range(2):
        for h in "qkvo":
            shapes[f"enc{i}.mha.{h}.w"], shapes[f"enc{i}.mha.{h}.b"] = (64, 64), (64,)
        for n in ("norm1", "norm2"):
            shapes[f"enc{i}.{n}.g"] = shapes[f"enc{i}.{n}.b"] = (64,)
        shapes[f"enc{i}.ff.l1.w"], shapes[f"enc{i}.ff.l1.b"] = (64, 128), (128,)
        shapes[f"enc{i}.ff.l2.w"], shapes[f"enc{i}.ff.l2.b"] = (128, 64), (64,)
    index = transformer.index(shapes, model, rate)
    p = {k: jnp.ones(tuple(len(a) for a in index[k]), jnp.float32) for k in shapes}
    tokens = jnp.zeros((1, 64), jnp.int32)
    got = _matmul_flops(
        lambda p_, t_: transformer.forward(
            p_, t_, rate, jax.random.key(0), heads=4, layers=2, dropout=0.2,
            mask_rate=0.15, mask_id=4000), p, tokens)
    want = flops.forward_flops(model, rate)
    assert want <= got <= 1.35 * want, (want, got)
