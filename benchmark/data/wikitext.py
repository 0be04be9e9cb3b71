"""Writer: a WikiText-2-shaped token corpus from a seed, as `wiki.*.tokens`
files under `wikitext-2/`, so that the program tokenises it with its normal
loader (whitespace split, `<eos>` per line, vocabulary from the train file).

The train file holds every word type once (so the vocabulary has exactly the
published number of types) and then Zipf-distributed draws, as natural text
has.  The program's vocabulary adds `<ukn>` and `<eos>`, so `types` - 2 word
types are written.
"""

import os

import numpy as np

LINE = 32  # words per line; each line also yields one <eos>


def _draw(rng, n_types, n):
    """n Zipf(1.0)-distributed type ids in [0, n_types)."""
    p = 1.0 / np.arange(1, n_types + 1, dtype=np.float64)
    cdf = np.cumsum(p / p.sum())
    return np.searchsorted(cdf, rng.random(n)).clip(0, n_types - 1)


def _write(path, words, ids):
    if len(ids) % LINE:
        raise ValueError("token count must fill whole lines")
    lines = words[ids].reshape(-1, LINE)
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(" ".join(row) for row in lines))
        f.write("\n")


def write(data_dir, data_name, seed, sizes):
    """``sizes``: {"types": vocabulary incl. <ukn> and <eos>, "train": tokens
    in the train stream incl. one <eos> per line, "test": likewise}.  Each
    count must be a multiple of LINE + 1.  (The program reads no valid file.)"""
    if data_name != "WikiText2":
        raise ValueError(f"wikitext writes WikiText2 only, not {data_name!r}")
    n_types = int(sizes["types"]) - 2
    words = np.array([f"w{i}" for i in range(n_types)])
    rng = np.random.default_rng([int(seed), 0x717])
    base = os.path.join(data_dir, data_name, "wikitext-2")
    os.makedirs(base, exist_ok=True)
    for split in ("train", "test"):
        total = int(sizes[split])
        if total % (LINE + 1):
            raise ValueError(f"{split}: {total} tokens are not whole lines "
                             f"of {LINE} words and one <eos>")
        n_words = total // (LINE + 1) * LINE
        if split == "train":
            if n_words < n_types:
                raise ValueError("train stream shorter than the vocabulary")
            ids = np.concatenate([np.arange(n_types),
                                  _draw(rng, n_types, n_words - n_types)])
            rng.shuffle(ids)  # the rare types spread over all users' rows
        else:
            ids = _draw(rng, n_types, n_words)
        _write(os.path.join(base, f"wiki.{split}.tokens"), words, ids)
