from bench import flops, run


def test_forward_macs_match_a_hand_count():
    # conv1 24*24*25*15 + conv2 8*8*375*28 + fc1 448*226 + fc2 226*10
    fm = run.load_json(run.BENCH, "configs", "paper-fmnist.json")
    assert flops.forward_macs(fm["model"]) == 991_508
    # conv1 28*28*75*15 + conv2 10*10*375*28 + fc1 700*294 + fc2 294*10
    cf = run.load_json(run.BENCH, "configs", "paper-cifar10.json")
    assert flops.forward_macs(cf["model"]) == 2_140_740


def test_training_counts_three_passes_of_two_operations():
    fm = run.load_json(run.BENCH, "configs", "paper-fmnist.json")
    assert flops.train_flops(fm["model"], 10) == 6 * 991_508 * 10
