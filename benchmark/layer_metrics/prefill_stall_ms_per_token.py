"""What a decoded token loses to somebody else's prefill: the summed duration
of the `mx.decode.prefill` spans, each times its `held` (the slots that were
decoding when it was launched), over the tokens the traced window decoded
(the summed `active` of its stepping `mx.decode.tick`s)."""
import program_spans


def read(run):
    got = program_spans.load(run)
    if not got:
        return None
    held = [(s.end - s.start) * s.args["held"] for s in got["spans"]
            if s.name == "mx.decode.prefill" and "held" in s.args]
    tokens = sum(s.args["active"] for s in got["spans"]
                 if s.name == "mx.decode.tick" and s.args.get("active"))
    if not held or not tokens:
        return None
    return sum(held) / 1e6 / tokens
