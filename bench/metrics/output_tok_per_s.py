"""output_tok_per_s: output tokens emitted inside the window over the
window's seconds."""


def read(run):
    return run.window_tokens() / run.seconds
