"""The one general traffic generator. A cell's file gives the parameters
(clip, prompts, words, values, clients); ``--seed`` only orders them, so every
seed sends the same set of requests in another order and does the same work.
"""

from __future__ import annotations

import itertools
import os

import numpy as np


def _insert(prompt: str, before: str, word: str) -> str:
    words = prompt.split(" ")
    i = words.index(before)
    return " ".join(words[:i] + [word] + words[i:])


def edit_request(cell: dict, root: str, word: str, value) -> dict:
    """One served-edit request of the cell's structure: the source prompt
    with ``word`` inserted, reweighted by ``value``."""
    src = cell["source_prompt"]
    return dict(
        cell["request"],
        image_path=os.path.join(root, cell["clip"]),
        prompt=src,
        prompts=[src, _insert(src, cell["insert_before"], word)],
        eq_params={"words": [word], "values": [value]},
        save_name=word,
    )


def setup_request(cell: dict, root: str) -> dict:
    s = cell["setup_request"]
    return edit_request(cell, root, s["insert_word"], s["eq_value"])


def edit_requests(cell: dict, root: str, seed: int):
    """An endless stream of the cell's requests: every (word, value) pair
    once, in an order drawn from the seed, then again."""
    pairs = list(itertools.product(cell["insert_words"], cell["eq_values"]))
    order = np.random.default_rng(int(seed)).permutation(len(pairs))
    for i in itertools.cycle(order):
        yield edit_request(cell, root, *pairs[int(i)])
