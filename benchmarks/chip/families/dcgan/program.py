"""The program's DCGAN, as the round engine takes it: the only file of
this family that imports the program."""
from __future__ import annotations

from repro.configs.dcgan import DCGANConfig
from repro.models.specs import make_dcgan_spec


def spec(cfg: dict):
    """The program's `GanModelSpec` for the configuration `cfg`."""
    return make_dcgan_spec(DCGANConfig(
        nz=cfg["nz"], ngf=cfg["ngf"], ndf=cfg["ndf"], nc=cfg["nc"],
        image_size=cfg["image_size"]))
