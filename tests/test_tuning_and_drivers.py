"""CLI driver smoke tests."""


def test_train_driver_cli_smoke():
    from repro.launch.train import main
    rc = main(["--arch", "rwkv6-3b", "--steps", "4", "--batch", "2",
               "--seq", "64"])
    assert rc == 0


def test_serve_driver_cli_smoke():
    from repro.launch.serve import main
    rc = main(["--arch", "mistral-nemo-12b", "--requests", "2", "--batch",
               "2", "--prompt-len", "8", "--new-tokens", "4", "--guard"])
    assert rc == 0


def test_serve_driver_decayed_guard_smoke():
    from repro.launch.serve import main
    rc = main(["--arch", "mistral-nemo-12b", "--requests", "2", "--batch",
               "2", "--prompt-len", "8", "--new-tokens", "4",
               "--guard-decay-every", "4"])
    assert rc == 0
