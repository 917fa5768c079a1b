"""Device time per train step of the ops under the models' ``head`` scope
(zoo.Model: the final norm, the tied vocabulary projection, the f32 log-softmax
and loss), forward and backward."""
from benchmarks.chip import scopes


def read(run):
    a = scopes.for_run(run, "train")
    return a.ms("head") if a else None
