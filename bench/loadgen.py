"""The one traffic generator: reads a mix's parameters, draws requests.

A mix is a JSON file ``bench/traffic/<name>.json`` of kind
``"sessions"``: ``contexts`` lists the prompt lengths of sessions that
are prefilled one at a time, in that order, during set-up; the window
then decodes them (``max_new_tokens`` each, more than a window serves).
Every seed gets the same lengths and its own prompt tokens, so two seeds
do the same amount of work.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Item:
    """One request: its prompt tokens and output budget."""
    rid: int
    prompt: list[int]
    max_new: int


def load_mix(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


def generate(mix: dict, seed: int, vocab: int) -> list[Item]:
    """The sessions of one run, in the order they are prefilled."""
    if mix["kind"] != "sessions":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    rng = np.random.default_rng(seed % 2 ** 63)
    return [Item(i, rng.integers(0, vocab, int(t)).tolist(),
                 int(mix["max_new_tokens"]))
            for i, t in enumerate(mix["contexts"])]


def max_context(mix: dict) -> int:
    """Longest prompt plus output budget any request of the mix may hold."""
    return max(mix["contexts"]) + mix["max_new_tokens"]
