"""Device time of the two ends of the decode step (`mx_head`: final norm,
unembedding over the vocabulary, argmax, the select of the previous step's
tokens, the counters; `mx_embed`: the embedding rows and position signal)
inside the runs of the decode step program, per run."""
import program_parts


def read(run):
    return program_parts.part_ms_a_run(run, program_parts.STEP,
                                       ("mx_head", "mx_embed"))
