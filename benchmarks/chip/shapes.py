"""Model sizes read from a configuration file's published ``config``.

The benchmark's own view of a model: the weights, the counts and the
reference read these sizes, never the program's registry.  A configuration
file names its family, and the family's file (``chip.family``) reads the
sizes into a ``Shape`` of its own that extends the one here.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Optional

from chip import family


@dataclasses.dataclass(frozen=True)
class Shape:
    """What every family has; a family's ``Shape`` adds its own sizes."""
    family: str                 # the family file's name
    layers: int
    d_model: int
    vocab: int
    norm_eps: float

    @property
    def vocab_rows(self) -> int:
        """Rows of the stored embedding table: the program pads the vocabulary
        to a multiple of 256 and masks the padded logits."""
        return -(-self.vocab // 256) * 256


def from_config(config: dict, chip_dir: Path = family.HERE) -> Shape:
    """Sizes of a configuration file (``configs/<name>.json``), read by the
    family it names under ``chip_dir``."""
    return family.find(config["family"], chip_dir).shape(config)


def common_sizes(shape: Shape) -> dict:
    """The program's ``ArchConfig`` attributes every family sets."""
    return {"n_layers": shape.layers, "d_model": shape.d_model,
            "vocab_size": shape.vocab, "norm_eps": shape.norm_eps}


def program_sizes(shape: Shape) -> dict:
    """The program's ``ArchConfig`` attributes that must equal these sizes
    (dotted paths into nested configs)."""
    return family.of(shape).program_sizes(shape)


def get_path(obj, path: str) -> Optional[object]:
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj
