"""Device milliseconds per train step in the model's head (the program's
``head`` phase, inside ``ge``): the final norm, the tied logits and the
float32 log-softmax of both forwards of every client."""
from chipbench import phases


def read(m):
    return phases.ms_per_step(m, "head")
