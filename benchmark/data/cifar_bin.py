"""Writer: CIFAR-10-shaped images from a seed, in the dataset's own on-disk
format (`cifar-10-batches-bin`: per record 1 label byte + 3072 pixel bytes,
channel-major), so that the program reads them with its normal loader.

Each class has one smooth 8x8 colour pattern, upsampled to 32x32; an image is
its class pattern at a random contrast plus per-pixel noise (`assumed` in the
configuration file: the published images are not in the sandbox).  The loss
can therefore fall during training, as it does on real CIFAR-10.

All records are made in one jitted call on the default device (on the chip:
milliseconds, where numpy on the host took 10 s of every run's set-up) and
come back as bytes; the same seed gives the same records.
"""

import os

import numpy as np

RECORD = 1 + 3 * 32 * 32


def records(seed, n, classes):
    """[n, RECORD] uint8 records from the seed."""
    import jax
    import jax.numpy as jnp

    def make(key):
        k_pat, k_lab, k_con, k_noise = jax.random.split(key, 4)
        coarse = jax.random.uniform(k_pat, (classes, 8, 8, 3), jnp.float32, 40.0, 215.0)
        patterns = coarse.repeat(4, axis=1).repeat(4, axis=2)  # [classes,32,32,3]
        labels = jax.random.randint(k_lab, (n,), 0, classes)
        contrast = jax.random.uniform(k_con, (n, 1, 1, 1), jnp.float32, 0.6, 1.0)
        noise = jax.random.normal(k_noise, (n, 32, 32, 3), jnp.float32) * 30.0
        img = 128.0 + (patterns[labels] - 128.0) * contrast + noise
        img = jnp.clip(img, 0.0, 255.0).astype(jnp.uint8)
        chw = img.transpose(0, 3, 1, 2).reshape(n, -1)
        return jnp.concatenate([labels.astype(jnp.uint8)[:, None], chw], axis=1)

    return np.asarray(jax.jit(make)(jax.random.key(int(seed) % (2 ** 63))))


def write(data_dir, data_name, seed, sizes):
    """Write train and test files under ``data_dir/<data_name>/``.

    ``sizes``: {"train": n, "test": n, "classes": c}.  Returns nothing; the
    program's loader finds the files.
    """
    if data_name != "CIFAR10":
        raise ValueError(f"cifar_bin writes CIFAR10 only, not {data_name!r}")
    n_train, n_test = int(sizes["train"]), int(sizes["test"])
    if n_train % 5:
        raise ValueError("train size must split into five batch files")
    rec = records(seed, n_train + n_test, int(sizes["classes"]))
    base = os.path.join(data_dir, data_name, "cifar-10-batches-bin")
    os.makedirs(base, exist_ok=True)
    per = n_train // 5
    for i in range(5):
        rec[i * per:(i + 1) * per].tofile(os.path.join(base, f"data_batch_{i + 1}.bin"))
    rec[n_train:].tofile(os.path.join(base, "test_batch.bin"))
