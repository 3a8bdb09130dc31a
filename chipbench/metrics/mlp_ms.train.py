"""Device milliseconds per train step in the model's feed-forward blocks
(the program's ``mlp`` phase, inside ``ge``): norm, both projections and
the activation of every layer of both forwards of every client."""
from chipbench import phases


def read(m):
    return phases.ms_per_step(m, "mlp")
