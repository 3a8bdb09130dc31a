"""Device milliseconds per train step in message apply (the program's
``ma`` phase, paper Table 4): the coefficients and the fold of every
client's message into the weights."""
from chipbench import phases


def read(m):
    return phases.ms_per_step(m, "ma")
