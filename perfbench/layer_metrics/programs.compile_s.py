"""Program store: wall seconds spent building programs during set-up, from
``program_store.compile_seconds`` (tracing, and compiling or retrieving)."""


def read(obs):
    return obs["programs"]["compile_s"]
