"""Engine / steps: programs compiled between the window's start and its
end — the larger of the program registry's count and jax's own
backend-compile events. Expected 0."""


def read(r):
    if "records" not in r:
        return None
    c = r["compiles"]
    return max(c["backend"], c["registry"])
