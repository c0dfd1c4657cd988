"""The command line's surface: which commands `cli` has, that each answers
`--help` before anything asks JAX for a device (the parent of a chip run
must not hold the chip), and that the commands of the CPU-ratio benchmarks
of PRs 1-20 are gone from the parser."""

import jax
import pytest

from solvingpapers_tpu.cli import main

pytestmark = pytest.mark.fast

COMMANDS = ("list", "train", "sample", "serve", "replay", "trace-summary",
            "eval", "export")


@pytest.mark.parametrize("cmd", COMMANDS)
def test_help_exits_zero_without_a_backend(cmd, monkeypatch, capsys):
    for ask in ("devices", "local_devices", "device_count", "default_backend"):
        monkeypatch.setattr(
            jax, ask, lambda *a, _ask=ask, **k: pytest.fail(f"jax.{_ask}()"))
    with pytest.raises(SystemExit) as e:
        main([cmd, "--help"])
    assert e.value.code == 0
    assert capsys.readouterr().out.startswith(f"usage: solvingpapers_tpu {cmd}")


@pytest.mark.parametrize("cmd", ["serve-bench", "kernel-bench"])
def test_removed_command_is_refused_by_the_parser(cmd, capsys):
    with pytest.raises(SystemExit) as e:
        main([cmd, "--config", "gpt_tiny"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "invalid choice" in err and all(c in err for c in COMMANDS)
