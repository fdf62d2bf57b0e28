"""A stand-in for the tiny MoE architecture's plain reference, copied into
a benchmark root as `bench/reference/tiny_moe.py` by the discovery test.
The program's capacity routing drops tokens, so no cell of this
architecture is checked yet."""


def make(cfg):
    raise NotImplementedError("no reference for the tiny MoE architecture")
