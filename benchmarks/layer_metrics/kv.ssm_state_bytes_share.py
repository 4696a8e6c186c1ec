"""The recurrent and convolution state among the bytes a decode round is reckoned to read and write (the program span engine.round's ssm_lanes x the state's bytes a lane, in and out, over counts_nemotron.decode_round_bytes of the same rounds, over the window's decoding rounds): what of a round the slots' state costs. Lower is fewer bytes."""


def read(c):
    from benchmarks import counts_nemotron, zaya_cell

    rs = [r for r in zaya_cell.moe_rounds(c) or [] if r.get("ssm_lanes")]
    if not rs:
        return None
    cfg = c["model_cfg"]
    lane = 2.0 * counts_nemotron.state_bytes_a_lane(cfg)
    state = sum(lane * r["ssm_lanes"] for r in rs)
    whole = sum(counts_nemotron.decode_round_bytes(
        cfg, r["live_tokens"] + r["active"], r["experts_touched"],
        r["ssm_lanes"]) for r in rs)
    return 100.0 * state / whole
