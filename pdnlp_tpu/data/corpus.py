"""Corpus loading and the seeded train/dev split.

Reference behavior being reproduced (not its code):
- ``load_data`` reads ``data/train.json`` — one JSON array of
  ``[text, label]`` pairs where the text is pre-tokenized with spaces —
  and re-joins by stripping the spaces (``single-gpu-cls.py:26-41``).
- The split takes the first 10,000 examples, shuffles them under seed 123,
  and cuts 92/8 into 9,200 train / 800 dev; dev doubles as the test set
  (``single-gpu-cls.py:226-247``).
"""
from __future__ import annotations

import json
import random
from typing import List, Sequence, Tuple

Example = Tuple[str, int]

# 6-class Chinese emotion labels (single-gpu-cls.py:212-219):
# other / like / sad / disgust / anger / happy
LABELS = ["其他", "喜好", "悲伤", "厌恶", "愤怒", "高兴"]
label2id = {name: i for i, name in enumerate(LABELS)}
id2label = {i: name for i, name in enumerate(LABELS)}


def load_data(path: str) -> List[Example]:
    """Read the corpus and strip pre-tokenization spaces."""
    try:
        f = open(path, encoding="utf-8")
    except FileNotFoundError as e:
        raise FileNotFoundError(
            f"no corpus at {path!r}: --data_path must name a JSON file "
            "holding one array of [text, label] pairs (the reference's "
            "data/train.json format: text optionally pre-tokenized with "
            f"spaces, label an int in 0..{len(LABELS) - 1}).  The default "
            "is the reference checkout's corpus, which is not part of this "
            "repository — pass your own file, or a generated one "
            "(chip_smoke.py and tests/conftest.py write seeded corpora in "
            "this format)") from e
    with f:
        raw = json.load(f)
    out: List[Example] = []
    for text, label in raw:
        text = "".join(text.split(" ")).strip()
        out.append((text, int(label)))
    return out


def split_data(
    data: Sequence[Example],
    seed: int = 123,
    limit: int = 10_000,
    ratio: float = 0.92,
) -> Tuple[List[Example], List[Example]]:
    """Seeded shuffle + split; returns (train, dev). Dev is also the test set."""
    data = list(data[:limit])
    rng = random.Random(seed)
    rng.shuffle(data)
    cut = int(len(data) * ratio)
    return data[:cut], data[cut:]
