"""Builder `hfdecoder`, test data: the program's GPT model for the
`hfdecoder` configuration, through the `gpt` builder under the
reference's translation of the keys."""
from builders import gpt
from references import hfdecoder


def build(cfg, seed, kind):
    return gpt.build(hfdecoder.as_gpt2(cfg), seed, kind)
