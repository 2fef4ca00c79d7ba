"""Training loop: programs compiled between the window's start and its
end (program registry and jax's backend-compile events, the larger).
Expected 0."""


def read(r):
    if "steps" not in r:
        return None
    c = r["compiles"]
    return max(c["backend"], c["registry"])
