"""The host runtime's executors waiting for their env steps, summed over
executor threads, per interval (``HostConfig(profile=True)``'s
``env_step_wait``)."""


def read(record):
    prof = record.get("host_profile")
    if not prof or "env_step_wait" not in prof:
        return None
    return 1e3 * prof["env_step_wait"] / record["intervals"]
