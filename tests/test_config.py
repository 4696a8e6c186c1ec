"""Config/flag-system tests (reference C18 parity)."""

import argparse
import dataclasses
import os
import sys

import pytest

from distributed_tensorflow_tpu.config import (
    ClusterConfig,
    DistributedRetrainConfig,
    MnistTrainConfig,
    RetrainConfig,
    ServeConfig,
    parse_flags,
)


def test_defaults_match_reference():
    m = MnistTrainConfig()
    assert m.training_steps == 10000 and m.batch_size == 100 and m.learning_rate == 1e-4
    r = RetrainConfig()
    assert r.training_steps == 10000 and r.learning_rate == 0.01
    assert r.testing_percentage == 10 and r.validation_percentage == 10
    assert r.train_batch_size == 100 and r.test_batch_size == -1
    assert DistributedRetrainConfig().training_steps == 2000
    c = ClusterConfig()
    assert c.job_name == "worker" and c.task_index == 0


def test_parse_flags_overrides():
    cfg = parse_flags(RetrainConfig, argv=["--learning_rate", "0.5", "--image_dir", "/x"])
    assert cfg.learning_rate == 0.5 and cfg.image_dir == "/x"
    assert cfg.training_steps == 10000  # untouched default


def test_parse_flags_tolerates_unknown():
    cfg = parse_flags(MnistTrainConfig, argv=["--training_steps", "5", "--bogus", "1"])
    assert cfg.training_steps == 5


def test_cluster_parsing():
    c = parse_flags(
        ClusterConfig,
        argv=["--worker_hosts", "a:1,b:2,c:3", "--task_index", "2", "--job_name", "worker"],
    )
    assert c.num_processes == 3
    assert c.coordinator_address == "a:1"
    assert not c.is_chief


def test_bool_flags():
    cfg = parse_flags(RetrainConfig, argv=["--flip_left_right"])
    assert cfg.flip_left_right is True
    assert parse_flags(RetrainConfig, argv=[]).flip_left_right is False


def test_serve_config_has_one_decode_cadence_and_no_monolithic_page():
    """A dispatch is one micro-step (run-ahead hides the host's round, so
    nothing fuses several): ``ServeConfig`` has no ``steps_per_sync``.
    ``page_size`` resolves to the engine's auto rule or an explicit size;
    a 0 is passed on as it came, for the engine to refuse by name."""
    names = {f.name for f in dataclasses.fields(ServeConfig)}
    assert "steps_per_sync" not in names
    with pytest.raises(TypeError, match="steps_per_sync"):
        ServeConfig(steps_per_sync=2)
    assert ServeConfig().engine_page_size is None
    assert ServeConfig(page_size=32).engine_page_size == 32
    assert ServeConfig(page_size=0).engine_page_size == 0
    help_text = ServeConfig.__dataclass_fields__["page_size"].metadata["help"]
    assert "monolithic" not in help_text


@pytest.mark.parametrize("tool,parsers", [("serve_lm", 2), ("loadgen", 1)])
def test_serving_tools_do_not_know_steps_per_sync(tool, parsers, monkeypatch):
    """No parser of either tool takes ``--steps_per_sync``: it is left over
    like any unknown flag (which these CLIs tolerate, as the reference's
    did: ``test_parse_flags_tolerates_unknown``) and sets nothing."""
    monkeypatch.syspath_prepend(
        os.path.join(os.path.dirname(__file__), "..", "tools"))
    main = __import__(tool).main
    parsed = []
    real = argparse.ArgumentParser.parse_known_args

    class Parsed(Exception):
        pass

    def spy(self, args=None, namespace=None):
        ns, rest = real(self, args, namespace)
        parsed.append((ns, rest))
        if len(parsed) == parsers:
            raise Parsed
        return ns, rest

    monkeypatch.setattr(argparse.ArgumentParser, "parse_known_args", spy)
    with pytest.raises(Parsed):
        main(["--slots", "2", "--steps_per_sync", "8"])
    assert len(parsed) == parsers
    for ns, rest in parsed:
        assert not hasattr(ns, "steps_per_sync")
        assert rest[-2:] == ["--steps_per_sync", "8"]
    assert parsed[-1][0].slots == 2  # a flag the tool does know is taken
