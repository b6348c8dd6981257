"""The host runtime's actor and stepper dispatches per interval
(``HostConfig(profile=True)``'s ``actor_dispatches`` plus
``step_dispatches``). Each interval has alpha x n_envs requests of each
kind, so fewer dispatches means wider batches: alpha each at the least,
alpha x n_envs at the most."""


def read(record):
    prof = record.get("host_profile")
    if not prof or "actor_dispatches" not in prof \
            or "step_dispatches" not in prof:
        return None
    return (prof["actor_dispatches"] + prof["step_dispatches"]) \
        / record["intervals"]
