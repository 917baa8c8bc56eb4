"""Programs compiled or traced inside the window: JAX's backend-compile
events there, plus the growth of the serve engine's trace counters where
the cell has them. Should read 0."""


def read(facts):
    return facts["window_compiles"] + sum(
        facts.get("window_traces", {}).values())
