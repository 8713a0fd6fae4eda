"""Share of the prefilled positions that hold a prompt token:
``prompt_tokens`` over ``prefill_positions`` (rows × padded width) of
``BatchServer.counters`` across the window, in %. The rest is the left
padding of shorter prompts to their wave's longest."""


def read(run):
    c = getattr(run, "counters", None)
    if not c or not c.get("prefill_positions"):
        return None
    return 100.0 * c["prompt_tokens"] / c["prefill_positions"]
