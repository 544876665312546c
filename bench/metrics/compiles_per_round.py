"""JAX compilations per window round, loads from the persistent cache left
out (the program's ``jax.compiles.<fun_name>`` counters)."""


def read(record):
    rounds = [r for r in record["rounds"] if "compiles" in r]
    if not rounds:
        return None
    return sum(sum(r["compiles"].values()) for r in rounds) / len(rounds)
