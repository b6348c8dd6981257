"""The host runtime's executors waiting for their actions, summed over
executor threads, per interval (``HostConfig(profile=True)``'s
``actor_wait``)."""


def read(record):
    prof = record.get("host_profile")
    if not prof or "actor_wait" not in prof:
        return None
    return 1e3 * prof["actor_wait"] / record["intervals"]
