"""Jitted prefill/decode: programs that went through the backend compiler
(or were loaded from its cache) inside the window, counted by a
`jax.monitoring` listener in the replica. Should read 0."""


def read(obs):
    return obs.get("compiles_in_window")
